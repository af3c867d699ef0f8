"""The configuration `fixed-brokerage-tpce5k` and its cell
`brokerage-tpce5k-sat` (PR 48). The benchmark's own runs do not run
these.

1. The configuration serves what `fixed-zipf-1k` serves but for
   `--symbols` and `--accounts`, under `fixed-cancel80-1k`'s seven
   guarantees and its `expect`, and nothing is cut.
2. The cell's files load, its traffic is `zipf1k-sat`'s but for the
   warm-up, and every layer metric BENCHMARK.json lists for it resolves
   to a file that reads it (and no other).
3. The stream is the program's `brokerage_stream`, reached as
   `module:function` and drawn lazily.
4. The control reference (books 128 deep) fails the byte comparison on
   the stream's first 200,000 messages by thousands of records.
5. `snapshot_cost.pos_gather_bytes` is `SeqConfig`'s plane and the
   live-entry program's chunk.
6. One rehearsal of the cell under the interpreter reads `correct: true`
   and with `--control` `correct: false`; each of the cell's
   heartbeat-read metrics reads a number from the rehearsal's
   heartbeats, and nothing from a program without the gauges. The
   deployment is patched down here to 48 symbols x 512 accounts and the
   warm-up to its preamble and four batches: 3,425 x 25,000 and 258,225
   messages are the chip's (2.7 GB of state does not belong on this
   sandbox's CPU). The marketable size is patched to 30 so that a hot
   side of so short a stream passes the control's 128."""

import inspect
import itertools
import os

import pytest

from benchmark import generators, judge, layers, run, snapshot_cost

CELL, CONFIG = "brokerage-tpce5k-sat", "fixed-brokerage-tpce5k"
NEW = {"brokerage_kernel_us_per_msg.sat", "brokerage_kernel_roofline.sat",
       "pos_gather_roofline.brokerage", "pos_gather_us_per_msg.brokerage",
       "snapshot_pos_fetch_mb.brokerage", "snapshot_pos_calls.brokerage",
       "snapshot_fetch_mb.brokerage", "live_positions.brokerage",
       "pos_probe_tiles_per_msg.brokerage",
       "checkpoint_ms_per_batch.brokerage",
       "snapshot_export_ms_per_batch.brokerage",
       "snapshot_meta_ms_per_batch.brokerage",
       "host_path_ms_per_batch.brokerage"}
FROM_TRACE = {"brokerage_kernel_us_per_msg.sat",
              "brokerage_kernel_roofline.sat",
              "pos_gather_roofline.brokerage",
              "pos_gather_us_per_msg.brokerage"}
PARAMS = {"num_symbols": 3425, "num_accounts": 25000, "zipf_a": 1.2,
          "account_zipf": 0.99, "take": 0.6, "cancel_share": 0.1,
          "standing": 512, "take_size": 150, "deposit": 1000000000}


def found_file(name):
    return run.load_json(os.path.join(layers.HERE, "layer_metrics",
                                      f"{name}.json"))


def test_serve_is_fixed_zipf_1ks_but_for_the_populations():
    _traffic, config = run.load_cell(CELL)
    _t, zipf = run.load_cell("zipf1k-sat")
    _t, c80 = run.load_cell("zipf1k-cancel80-sat")
    assert config["name"] == CONFIG and zipf["name"] == "fixed-zipf-1k"
    serve, theirs = list(config["serve"]), list(zipf["serve"])
    for flag, mine, other in (("--symbols", "3425", "1024"),
                              ("--accounts", "25000", "2048")):
        i = serve.index(flag)
        assert serve[i + 1] == mine and theirs[i + 1] == other
        serve[i + 1] = theirs[i + 1] = None
    assert serve == theirs
    assert config["guarantees"] == c80["guarantees"]
    assert len(config["guarantees"]) == 7
    assert config["reference"] == zipf["reference"]
    assert config["control"]["reference"] == {
        "compat": "fixed", "book_slots": 128, "max_fills": 16}
    assert config["expect"] == c80["expect"]
    assert config["reduced"] == []
    assert config["stream"]["params"].keys() <= config["assumed"].keys() \
        | {"num_symbols", "num_accounts", "zipf_a", "account_zipf",
           "take", "cancel_share", "standing", "take_size", "deposit"}
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert len(entry["source"]) <= 200
    assert "TPC-E" in entry["source"] and "YCSB" in entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_cell_files_load_and_its_layer_metrics_resolve():
    traffic, _config = run.load_cell(CELL)
    zipf, _c = run.load_cell("zipf1k-sat")
    assert (traffic["name"], traffic["config"]) == (CELL, CONFIG)
    assert traffic["warmup_messages"] == 2 * 25000 + 3425 + 100 * 2048
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
                "host_path_ms_per_batch.sat", "snapshot_fetch_mb.sat",
                "cancel80_kernel_roofline.sat"} & set(found)
    # the kernel's share is taken against the same least bytes
    assert found["brokerage_kernel_roofline.sat"]["read"] \
        == found_file("seq_kernel_roofline.sat")["read"]
    # the reads the other fixed cells' files make, under this cell's names
    for mine, theirs in (
            ("snapshot_fetch_mb.brokerage", "snapshot_fetch_mb.sat"),
            ("pos_probe_tiles_per_msg.brokerage",
             "pos_probe_tiles_per_msg.sat"),
            ("checkpoint_ms_per_batch.brokerage",
             "checkpoint_ms_per_batch.cancel80"),
            ("snapshot_export_ms_per_batch.brokerage",
             "snapshot_export_ms_per_batch.vmem"),
            ("snapshot_meta_ms_per_batch.brokerage",
             "snapshot_meta_ms_per_batch.cancel80"),
            ("host_path_ms_per_batch.brokerage",
             "host_path_ms_per_batch.cancel80")):
        assert found[mine]["read"] == found_file(theirs)["read"], mine


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_stream_is_the_programs_generator_drawn_lazily(seed):
    from kme_tpu.workload import brokerage_stream

    _traffic, config = run.load_cell(CELL)
    s = config["stream"]
    assert s["generator"] == "kme_tpu.workload:brokerage_stream"
    assert s["events"] == 2000000 and s["params"] == PARAMS
    # the tuned values are the generator's defaults
    defaults = inspect.signature(brokerage_stream).parameters
    for k, v in PARAMS.items():
        if k not in ("num_symbols", "num_accounts"):
            assert defaults[k].default == v, k
    # a generator function: open_stream returns before anything is
    # drawn, and client.Stream's thread draws beside the server's start
    assert inspect.isgeneratorfunction(brokerage_stream)
    n = 53425 + 4000
    got = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], seed, s["params"]), n))
    want = list(itertools.islice(brokerage_stream(
        4000, 3425, 25000, seed=seed), n))
    assert len(want) == n and got == want


def test_control_reference_fails_the_comparison_by_thousands():
    _traffic, config = run.load_cell(CELL)
    s = config["stream"]
    msgs = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], 2 ** 31 + 3, s["params"]), 200000))
    want = judge.make_reference(config["reference"]).process_wire(msgs)
    ctrl = judge.make_reference(
        config["control"]["reference"]).process_wire(msgs)
    flat = lambda groups: [ln for g in groups for ln in g]  # noqa: E731
    assert judge.differing(flat(want), flat(want)) == 0
    assert judge.differing(flat(ctrl), flat(want)) > 2000


@pytest.mark.parametrize("cell", [CELL, "zipf1k-sat", "vmem-default-sat"])
def test_pos_gather_bytes_are_seq_configs(cell):
    """The plane is SeqConfig.pos_rows x 128 words, the chunk
    seq.live_positions_chunk entries of an index and four words."""
    from kme_tpu.engine import seq as SQ

    _traffic, config = run.load_cell(cell)
    lanes, accounts = snapshot_cost.store_shape(config)
    cfg = SQ.SeqConfig(lanes=lanes, accounts=accounts, slots=128)
    assert snapshot_cost.pos_plane_bytes(config) == cfg.pos_rows * SQ.LN * 4
    assert snapshot_cost.live_positions_chunk(config) \
        == SQ.live_positions_chunk(cfg)
    assert snapshot_cost.pos_gather_bytes(config) == (
        cfg.pos_rows * SQ.LN * 4 + 4 + SQ.live_positions_chunk(cfg) * 20)
    if cell == CELL:
        assert (lanes, accounts) == (3425, 25088)
        assert snapshot_cost.pos_plane_bytes(config) == 1374822400
        assert snapshot_cost.live_positions_chunk(config) == 262144


SMALL = {"--symbols": "48", "--accounts": "512"}


def patched_cell():
    """The cell at a size the interpreter serves inside a window."""
    traffic, config = run.load_cell(CELL)
    for flag, value in SMALL.items():
        config["serve"][config["serve"].index(flag) + 1] = value
    config["stream"]["params"].update(
        num_symbols=48, num_accounts=512, standing=64, take_size=30)
    traffic["warmup_messages"] = 2 * 512 + 48 + 4 * 2048
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
    # four need a device trace, which the CPU does not give)
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
    for key in ("snapshot_pos_fetch_bytes", "snapshot_pos_calls",
                "snapshot_fetch_bytes", "snapshot_live_positions"):
        assert key in hbs["hb_b"]["metrics"]["gauges"], key
    value = layers.read(spec["read"], hbs)
    assert isinstance(value, (int, float)) and value >= 0, (name, value)
    assert result["metrics"][f"cpu_rehearsal.{name}"]["value"] == value
    if name == "snapshot_pos_calls.brokerage":
        assert value == 1
    if name == "snapshot_pos_fetch_mb.brokerage":
        # one chunk of 8,192 entries, not the plane of 48 x 2 tiles
        assert value == pytest.approx((4 + 8192 * 20) * 1e-6)
    if name == "live_positions.brokerage":
        assert 1000 < value < 48 * 512
    # and nothing, without raising, from a program without them
    bare = {k: dict(hb, metrics={"counters": {
        "service_batches": hb["metrics"]["counters"]["service_batches"],
        "service_records": hb["metrics"]["counters"]["service_records"]},
        "gauges": {}}) for k, hb in hbs.items()}
    assert layers.read(spec["read"], bare) is None
