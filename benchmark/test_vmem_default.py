"""The configuration `fixed-vmem-default` and its cell `vmem-default-sat`
(PR 29). The benchmark's own runs do not run these.

1. The configuration serves what `kme-serve` serves with no flags: each
   value of its `serve` equals the parser's default.
2. The cell's files load, and every layer metric BENCHMARK.json lists
   for it resolves to a file that reads it (and no other).
3. The stream is `kme_tpu.workload.zipf_symbol_stream` at `zipf_a` 0.0,
   message for message.
4. The control reference fails the byte comparison on the stream's
   first 12,000 messages.
5. One whole rehearsal of the cell under the interpreter reads
   `correct: true`, and with `--control` `correct: false`. The warm-up
   is patched down here: 153,600 messages are the chip's, the cell file
   keeps them."""

import itertools

import pytest

from benchmark import generators, judge, kernel_cost, layers, run

CELL, CONFIG = "vmem-default-sat", "fixed-vmem-default"


def test_serve_values_are_the_parsers_defaults():
    from kme_tpu.bridge import serve

    _traffic, config = run.load_cell(CELL)
    given = serve.build_parser().parse_args(config["serve"])
    default = serve.build_parser().parse_args(["--auto-provision"])
    assert vars(given) == vars(default)
    # every default that shapes the engine is written out, none implied
    for flag in ("--engine", "--compat", "--symbols", "--accounts",
                 "--slots", "--max-fills", "--batch", "--pipeline",
                 "--checkpoint-every"):
        assert flag in config["serve"], flag
    assert kernel_cost.serve_option(config, "--compat") == "fixed"
    assert config["reduced"] == []


def test_cell_files_load_and_its_layer_metrics_resolve():
    traffic, config = run.load_cell(CELL)
    assert (traffic["name"], traffic["config"]) == (CELL, CONFIG)
    assert traffic["kind"] == "saturate"
    assert traffic["warmup_messages"] == 5120 + 145 * 1024
    entry, e2e, per_layer = run.benchmark_entry(CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert {m["name"] for m in e2e} == {"orders_per_s", "setup_s"}
    reports = {m["name"] for m in e2e}
    found = {m["name"]: m for m in layers.load_for(CELL, reports)}
    # (a list-less metric of BENCHMARK.json is the cell's only where
    # the cell reports the end-to-end metric it moves)
    per_layer = [m for m in per_layer if m["moves"] in reports]
    assert set(found) == {m["name"] for m in per_layer}
    new = {"pos_load_pct.sat", "pos_probe_tiles_per_msg.sat",
           "vmem_kernel_us_per_msg.sat", "vmem_kernel_roofline.sat",
           "process_wire_ms_per_batch.sat", "produce_lines_ms_per_batch.sat",
           "checkpoint_ms_per_batch.vmem",
           "snapshot_export_ms_per_batch.vmem"}
    assert new <= set(found)
    for m in per_layer:
        f = found[m["name"]]
        assert all(f[k] == m[k] for k in ("unit", "better", "source",
                                          "layer", "moves")), m["name"]
        if m["name"] in new:
            assert f["cells"] == m["workloads"] == [CELL]
    # no file of another cell's list takes this cell up
    assert not {"kernel_us_per_msg.sat", "seq_kernel_roofline.sat",
                "lane_switches_per_msg.sat"} & set(found)


def test_new_layer_metrics_read_a_heartbeat_and_skip_an_old_one():
    """Each heartbeat-read metric finds its number in a heartbeat of
    this PR's program, and reads None (the line leaves it out) in one
    of a program without the gauges and the counter."""
    a = {"time": 10.0, "metrics": {
        "counters": {"service_batches": 4, "service_records": 4096,
                     "pos_probe_tiles": 1000},
        "gauges": {"pos_load_pct": 2.5, "process_wire_s": 0.1,
                   "produce_lines_s": 0.4, "checkpoint_s": 0.5,
                   "snapshot_export_s": 0.1}}}
    b = {"time": 40.0, "metrics": {
        "counters": {"service_batches": 104, "service_records": 106496,
                     "pos_probe_tiles": 257000},
        "gauges": {"pos_load_pct": 4.0, "process_wire_s": 3.1,
                   "produce_lines_s": 15.4, "checkpoint_s": 14.5,
                   "snapshot_export_s": 3.9}}}
    specs = {m["name"]: m for m in layers.load_for(CELL, {"orders_per_s"})}

    def read(name, hb_a, hb_b):
        return layers.read(specs[name]["read"], {"hb_a": hb_a, "hb_b": hb_b})

    assert read("pos_load_pct.sat", a, b) == 4.0
    assert read("pos_probe_tiles_per_msg.sat", a, b) == 2.5
    assert read("process_wire_ms_per_batch.sat", a, b) == pytest.approx(30)
    assert read("produce_lines_ms_per_batch.sat", a, b) == pytest.approx(150)
    assert read("checkpoint_ms_per_batch.vmem", a, b) == pytest.approx(140)
    assert read("snapshot_export_ms_per_batch.vmem", a, b) \
        == pytest.approx(38)
    old = [{"time": h["time"], "metrics": {
        "counters": {k: v for k, v in h["metrics"]["counters"].items()
                     if k != "pos_probe_tiles"},
        "gauges": {k: v for k, v in h["metrics"]["gauges"].items()
                   if k != "pos_load_pct"}}} for h in (a, b)]
    assert read("pos_load_pct.sat", *old) is None
    assert read("pos_probe_tiles_per_msg.sat", *old) is None


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_stream_is_the_programs_generator_at_zipf_a_zero(seed):
    from kme_tpu.workload import zipf_symbol_stream

    _traffic, config = run.load_cell(CELL)
    s = config["stream"]
    assert s["params"] == {"num_symbols": 1024, "num_accounts": 2048,
                           "zipf_a": 0.0}
    n = 5120 + 4000
    got = list(itertools.islice(generators.open_stream(
        s["generator"], s["events"], seed, s["params"]), n))
    want = zipf_symbol_stream(4000, 1024, 2048, seed=seed, zipf_a=0.0)
    assert len(want) == n and got == list(want)


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


@pytest.mark.parametrize("control", [False, True])
def test_rehearsal_of_the_cell(tmp_path, monkeypatch, control):
    traffic, config = run.load_cell(CELL)
    traffic["warmup_messages"] = 5120 + 1024
    monkeypatch.setattr(run, "load_cell", lambda cell: (traffic, config))
    result = run.run_cell(CELL, seed=11, seconds=12, trace=False,
                          allow_cpu=True, events=12000, control=control,
                          out=str(tmp_path / "run"))
    assert result["correct"] is not control
    assert result["failed"] == 0 and result["rehearsal"] == "cpu"
    assert set(result["metrics"]) == {"cpu_rehearsal.orders_per_s",
                                      "cpu_rehearsal.setup_s"}
