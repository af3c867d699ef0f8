"""The configuration `fixed-lifecycle-1k` and its cell `lifecycle1k-sat`
(PR 36). The benchmark's own runs do not run these.

1. The configuration serves what `fixed-zipf-1k` serves: its `serve`
   line is that one's word for word, its guarantees that one's six and
   two more, and nothing is cut.
2. The cell's files load, its traffic is `zipf1k-sat`'s but for the
   warm-up, and every layer metric BENCHMARK.json lists for it resolves
   to a file that reads it (and no other).
3. The stream is the program's `market_lifecycle_stream`, reached as
   `module:function`.
4. The control reference fails the byte comparison on the stream's
   first 12,000 messages.
5. One rehearsal of the cell under the interpreter reads `correct: true`
   and with `--control` `correct: false`; each of the cell's new
   heartbeat-read metrics reads a number from the rehearsal's
   heartbeats, and nothing from a program without the counters. The
   warm-up is patched down here: 107,520 messages are the chip's."""

import itertools
import json
import os

import pytest

from benchmark import generators, judge, layers, run

CELL, CONFIG = "lifecycle1k-sat", "fixed-lifecycle-1k"
NEW = {"lanes_reused_per_listing.lifecycle",
       "route_purge_ms_per_batch.lifecycle",
       "host_path_ms_per_batch.lifecycle",
       "wiped_orders_per_settlement.lifecycle",
       "checkpoint_ms_per_batch.lifecycle",
       "lifecycle_kernel_us_per_msg.sat", "lifecycle_kernel_roofline.sat"}
FROM_TRACE = {"lifecycle_kernel_us_per_msg.sat",
              "lifecycle_kernel_roofline.sat"}


def test_serve_is_fixed_zipf_1ks_and_nothing_is_cut():
    _traffic, config = run.load_cell(CELL)
    _t, zipf = run.load_cell("zipf1k-sat")
    assert config["name"] == CONFIG and zipf["name"] == "fixed-zipf-1k"
    assert config["serve"] == zipf["serve"]
    assert config["guarantees"][:6] == zipf["guarantees"]
    assert len(config["guarantees"]) == 8
    assert config["reference"] == zipf["reference"]
    assert config["control"] == zipf["control"]
    assert config["expect"] == zipf["expect"]
    assert config["reduced"] == []
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["reduced"] == []
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
                "vmem_kernel_roofline.sat"} & set(found)


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_stream_is_the_programs_generator(seed):
    from kme_tpu.workload import market_lifecycle_stream

    _traffic, config = run.load_cell(CELL)
    s = config["stream"]
    assert s["generator"] == "kme_tpu.workload:market_lifecycle_stream"
    assert s["events"] == 900000
    assert s["params"] == {"num_symbols": 1024, "num_accounts": 2048,
                           "zipf_a": 1.2}
    n = 5120 + 4000
    got = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], seed, s["params"]), n))
    want = list(itertools.islice(market_lifecycle_stream(
        4000, 1024, 2048, seed=seed, zipf_a=1.2), n))
    assert len(want) == n and got == want


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 3])
def test_control_reference_fails_the_comparison(seed):
    _traffic, config = run.load_cell(CELL)
    s = config["stream"]
    msgs = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], seed, s["params"]), 12000))
    want = judge.make_reference(config["reference"]).process_wire(msgs)
    ctrl = judge.make_reference(
        config["control"]["reference"]).process_wire(msgs)
    flat = lambda groups: [ln for g in groups for ln in g]  # noqa: E731
    assert judge.differing(flat(want), flat(want)) == 0
    assert judge.differing(flat(ctrl), flat(want)) > 0


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """`--allow-cpu --events 30000 --seconds 6` (about 30 settlements;
    the interpreter serves 6,000 events in less than a window), the
    warm-up patched down to the preamble and half a batch; keeps the
    heartbeats the layer metrics read."""
    traffic, config = run.load_cell(CELL)
    traffic["warmup_messages"] = 5120 + 1024
    kept = {}
    read = layers.read

    def keeping(spec, ctx):
        kept.update(hb_a=ctx["hb_a"], hb_b=ctx["hb_b"])
        return read(spec, ctx)

    mp = pytest.MonkeyPatch()
    mp.setattr(run, "load_cell", lambda cell: (traffic, config))
    mp.setattr(layers, "read", keeping)
    try:
        result = run.run_cell(
            CELL, seed=2 ** 31 + 11, seconds=6, trace=True, allow_cpu=True,
            events=30000, out=str(tmp_path_factory.mktemp("run")))
    finally:
        mp.undo()
    return result, kept


def test_rehearsal_of_the_cell(rehearsal):
    result, _hbs = rehearsal
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["rehearsal"] == "cpu"
    # a traced run's line carries the layer metrics (the trace's own
    # two need a device trace, which the CPU does not give)
    assert set(result["metrics"]) >= {
        f"cpu_rehearsal.{n}" for n in NEW - FROM_TRACE}


def test_control_rehearsal_is_not_correct(tmp_path, monkeypatch):
    traffic, config = run.load_cell(CELL)
    traffic["warmup_messages"] = 5120 + 1024
    monkeypatch.setattr(run, "load_cell", lambda cell: (traffic, config))
    result = run.run_cell(CELL, seed=2 ** 31 + 11, seconds=3, trace=False,
                          allow_cpu=True, events=20000, control=True,
                          out=str(tmp_path / "run"))
    assert result["correct"] is False
    assert set(result["metrics"]) == {"cpu_rehearsal.orders_per_s",
                                      "cpu_rehearsal.setup_s"}


@pytest.mark.parametrize("name", sorted(NEW - FROM_TRACE))
def test_new_metric_reads_the_rehearsals_heartbeats(name, rehearsal):
    result, hbs = rehearsal
    spec = json.load(open(os.path.join(layers.HERE, "layer_metrics",
                                       f"{name}.json")))
    for hb in (hbs["hb_a"], hbs["hb_b"]):
        # the counters and gauges are there from the first batch on
        for key in ("symbols_listed", "symbols_settled", "lanes_released",
                    "lanes_reused", "unlisted_rejects",
                    "barrier_wiped_orders", "barrier_credited_positions"):
            assert key in hb["metrics"]["counters"], key
        for key in ("lanes_bound", "lanes_free", "route_purge_s",
                    "route_purge_n"):
            assert key in hb["metrics"]["gauges"], key
    value = layers.read(spec["read"], hbs)
    assert isinstance(value, (int, float)) and value >= 0, (name, value)
    assert result["metrics"][f"cpu_rehearsal.{name}"]["value"] == value
    if name == "lanes_reused_per_listing.lifecycle":
        assert value == 1.0
    # and nothing, without raising, from a program without them
    bare = {k: dict(hb, metrics={"counters": {
        "service_batches": hb["metrics"]["counters"]["service_batches"]},
        "gauges": {}}) for k, hb in hbs.items()}
    assert layers.read(spec["read"], bare) is None
