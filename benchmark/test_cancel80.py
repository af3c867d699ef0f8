"""The configuration `fixed-cancel80-1k` and its cell
`zipf1k-cancel80-sat` (PR 41). The benchmark's own runs do not run these.

1. The configuration serves what `fixed-zipf-1k` serves: its `serve`
   line is that one's word for word, its guarantees that one's six and
   one more, its `expect` that one's and `stale_routes` at most 0, and
   nothing is cut.
2. The cell's files load, its traffic is `zipf1k-sat`'s but for the
   warm-up, and every layer metric BENCHMARK.json lists for it resolves
   to a file that reads it (and no other).
3. The stream is the program's `quote_churn_stream`, reached as
   `module:function` and drawn lazily.
4. The control reference fails the byte comparison on the stream's
   first 60,000 messages (takers lift about four quotes, the control
   stops a taker after two), by thousands of records.
5. One rehearsal of the cell under the interpreter reads `correct: true`
   — `final stale_routes` at most 0 among its comparisons — and with
   `--control` `correct: false`; each of the cell's new heartbeat-read
   metrics reads a number from the rehearsal's heartbeats, and nothing
   from a program without the counters. The warm-up and `standing` are
   patched down here: 107,520 messages and 32,768 orders are the
   chip's."""

import inspect
import itertools
import os

import pytest

from benchmark import generators, judge, layers, run

CELL, CONFIG = "zipf1k-cancel80-sat", "fixed-cancel80-1k"
NEW = {"route_drop_ms_per_batch.cancel80",
       "routes_dropped_per_msg.cancel80", "routes_held.cancel80",
       "stale_routes.cancel80", "snapshot_meta_ms_per_batch.cancel80",
       "host_path_ms_per_batch.cancel80",
       "checkpoint_ms_per_batch.cancel80",
       "cancel80_kernel_us_per_msg.sat", "cancel80_kernel_roofline.sat"}
FROM_TRACE = {"cancel80_kernel_us_per_msg.sat",
              "cancel80_kernel_roofline.sat"}


def test_serve_is_fixed_zipf_1ks_and_nothing_is_cut():
    _traffic, config = run.load_cell(CELL)
    _t, zipf = run.load_cell("zipf1k-sat")
    assert config["name"] == CONFIG and zipf["name"] == "fixed-zipf-1k"
    assert config["serve"] == zipf["serve"]
    assert config["guarantees"][:6] == zipf["guarantees"]
    assert len(config["guarantees"]) == 7
    assert "one route for each order resting" in config["guarantees"][6]
    assert config["reference"] == zipf["reference"]
    assert config["control"]["reference"] == zipf["control"]["reference"]
    assert config["expect"] == {
        "engine": "seq", "pipeline": 2,
        "metrics_at_most": {"rej_capacity": 0, "stale_routes": 0}}
    assert config["reduced"] == []
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert len(entry["source"]) <= 200
    assert "BASELINE.json configs[3]" in entry["source"]
    assert "LOBSTER" in entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_cell_files_load_and_its_layer_metrics_resolve():
    traffic, _config = run.load_cell(CELL)
    zipf, _c = run.load_cell("zipf1k-sat")
    assert (traffic["name"], traffic["config"]) == (CELL, CONFIG)
    assert traffic["warmup_messages"] == 5120 + 50 * 2048
    for key in ("kind", "lead_orders", "chunk", "consumer_pause_ms"):
        assert traffic[key] == zipf[key], key
    entry, e2e, per_layer = run.benchmark_entry(CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    reports = {m["name"] for m in e2e}
    assert reports == {"orders_per_s", "setup_s"}
    found = {m["name"]: m for m in layers.load_for(CELL, reports)}
    per_layer = [m for m in per_layer if m["moves"] in reports]
    assert set(found) == {m["name"] for m in per_layer}
    assert NEW <= set(found)
    for m in per_layer:
        f = found[m["name"]]
        assert all(f[k] == m[k] for k in ("unit", "better", "source",
                                          "layer", "moves")), m["name"]
        if m["name"] in NEW:
            assert f["cells"] == m["workloads"] == [CELL]
            assert m["moves"] == "orders_per_s"
    # no file of another cell's list takes this cell up
    assert not {"kernel_us_per_msg.sat", "seq_kernel_roofline.sat",
                "host_path_ms_per_batch.sat",
                "lifecycle_kernel_roofline.sat"} & set(found)
    # the kernel's share is taken against the same least bytes
    assert found["cancel80_kernel_roofline.sat"]["read"] \
        == found_file("seq_kernel_roofline.sat")["read"]


def found_file(name):
    return run.load_json(os.path.join(layers.HERE, "layer_metrics",
                                      f"{name}.json"))


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_stream_is_the_programs_generator_drawn_lazily(seed):
    from kme_tpu.workload import quote_churn_stream

    _traffic, config = run.load_cell(CELL)
    s = config["stream"]
    assert s["generator"] == "kme_tpu.workload:quote_churn_stream"
    assert s["events"] == 1500000
    assert s["params"] == {"num_symbols": 1024, "num_accounts": 2048,
                           "zipf_a": 1.2, "cancel_ratio": 0.8,
                           "standing": 32768, "take": 0.05}
    # a generator function: open_stream returns before anything is
    # drawn, and client.Stream's thread draws beside the server's start
    assert inspect.isgeneratorfunction(quote_churn_stream)
    n = 5120 + 4000
    got = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], seed, s["params"]), n))
    want = list(itertools.islice(quote_churn_stream(
        4000, 1024, 2048, seed=seed), n))
    assert len(want) == n and got == want


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_control_reference_fails_the_comparison_by_thousands(seed):
    _traffic, config = run.load_cell(CELL)
    s = config["stream"]
    msgs = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], seed, s["params"]), 60000))
    want = judge.make_reference(config["reference"]).process_wire(msgs)
    ctrl = judge.make_reference(
        config["control"]["reference"]).process_wire(msgs)
    flat = lambda groups: [ln for g in groups for ln in g]  # noqa: E731
    assert judge.differing(flat(want), flat(want)) == 0
    assert judge.differing(flat(ctrl), flat(want)) > 2000


def patched_cell():
    """The cell at a size the interpreter serves inside a window: the
    pool stands at 1,024 orders and the window opens after the preamble
    and four batches."""
    traffic, config = run.load_cell(CELL)
    traffic["warmup_messages"] = 5120 + 4 * 2048
    config["stream"]["params"]["standing"] = 1024
    return traffic, config


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """`--allow-cpu --events 40000 --seconds 8`; keeps the heartbeats
    the layer metrics read."""
    cell = patched_cell()
    kept = {}
    read = layers.read

    def keeping(spec, ctx):
        kept.update(hb_a=ctx["hb_a"], hb_b=ctx["hb_b"])
        return read(spec, ctx)

    mp = pytest.MonkeyPatch()
    mp.setattr(run, "load_cell", lambda name: cell)
    mp.setattr(layers, "read", keeping)
    try:
        result = run.run_cell(
            CELL, seed=2 ** 31 + 11, seconds=8, trace=True, allow_cpu=True,
            events=40000, out=str(tmp_path_factory.mktemp("run")))
    finally:
        mp.undo()
    return result, kept


def test_rehearsal_of_the_cell(rehearsal):
    result, _hbs = rehearsal
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["rehearsal"] == "cpu"
    for what in ("final stale_routes", "final rej_capacity"):
        assert result["compared"][what] == {"value": 0, "limit": 0}, what
    # a traced run's line carries the layer metrics (the trace's own
    # two need a device trace, which the CPU does not give)
    assert set(result["metrics"]) >= {
        f"cpu_rehearsal.{n}" for n in NEW - FROM_TRACE}


def test_control_rehearsal_is_not_correct(tmp_path, monkeypatch):
    cell = patched_cell()
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    result = run.run_cell(CELL, seed=2 ** 31 + 11, seconds=8, trace=False,
                          allow_cpu=True, events=40000, control=True,
                          out=str(tmp_path / "run"))
    assert result["correct"] is False
    assert set(result["metrics"]) == {"cpu_rehearsal.orders_per_s",
                                      "cpu_rehearsal.setup_s"}


@pytest.mark.parametrize("name", sorted(NEW - FROM_TRACE))
def test_new_metric_reads_the_rehearsals_heartbeats(name, rehearsal):
    result, hbs = rehearsal
    spec = found_file(name)
    for hb in (hbs["hb_a"], hbs["hb_b"]):
        # the counters and gauges are there from the first batch on
        for key in ("routes_made", "routes_dropped", "cancels_routed",
                    "cancels_host_rejected"):
            assert key in hb["metrics"]["counters"], key
        for key in ("routes_held", "route_drop_s", "route_drop_n"):
            assert key in hb["metrics"]["gauges"], key
    assert "stale_routes" in hbs["hb_b"]["metrics"]["gauges"]
    value = layers.read(spec["read"], hbs)
    assert isinstance(value, (int, float)) and value >= 0, (name, value)
    assert result["metrics"][f"cpu_rehearsal.{name}"]["value"] == value
    if name == "stale_routes.cancel80":
        assert value == 0
    if name == "routes_dropped_per_msg.cancel80":
        assert 0.35 < value < 0.65
    if name == "routes_held.cancel80":
        # the pool of 1,024 less the orders already filled, and up to
        # two batches' new routes the router is ahead by
        assert 500 < value < 1024 + 2 * 2048
    # and nothing, without raising, from a program without them
    bare = {k: dict(hb, metrics={"counters": {
        "service_batches": hb["metrics"]["counters"]["service_batches"],
        "service_records": hb["metrics"]["counters"]["service_records"]},
        "gauges": {}}) for k, hb in hbs.items()}
    assert layers.read(spec["read"], bare) is None
