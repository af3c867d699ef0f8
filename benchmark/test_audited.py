"""The configuration `fixed-audited-1k` and its cell `zipf1k-audited-sat`
(PR 50). The benchmark's own runs do not run these.

1. The configuration serves what `fixed-zipf-1k` serves plus the five
   flags of README's "Flight recorder & audit" and "Continuous
   profiling" recipes, over the same stream, under its six guarantees
   and two more, and nothing is cut.
2. The cell's files load, its traffic is `zipf1k-sat`'s but for the
   names and the `why`, and every layer metric BENCHMARK.json lists for
   it resolves to a file that reads it (and no other).
3. One rehearsal of the cell under the interpreter reads `correct: true`,
   with `--control` `correct: false`, and under `KME_AUDIT_TAMPER=
   fill_qty` `correct: false` by `heartbeat degraded`; each of the
   cell's heartbeat-read metrics reads a number from the rehearsal's
   heartbeats (the auditor saw every batch; the snapshot made one fetch),
   and nothing from a program without the gauges. The deployment is
   patched down here to 32 symbols x 256 accounts x 4,096 slots and the
   warm-up to its preamble and half a batch: 1,024 x 2,048 x 8,192 is
   the chip's."""

import os

import pytest

from benchmark import layers, run

CELL, CONFIG = "zipf1k-audited-sat", "fixed-audited-1k"
FLAGS = ["--journal-out", "{checkpoint_dir}/journal.kmej",
         "--journal-fsync", "batch", "--audit",
         "--audit-repro-dir", "{checkpoint_dir}/repro",
         "--tsdb", "{checkpoint_dir}/tsdb"]
FROM_TRACE = {"audited_kernel_us_per_msg.sat", "audited_kernel_roofline.sat"}
SAME_READ = {
    "snapshot_fetch_calls.audited": "snapshot_fetch_calls.sat",
    "snapshot_fetch_mb.audited": "snapshot_fetch_mb.sat",
    "audited_kernel_us_per_msg.sat": "kernel_us_per_msg.sat",
    "audited_kernel_roofline.sat": "seq_kernel_roofline.sat",
    "checkpoint_ms_per_batch.audited": "checkpoint_ms_per_batch.sat",
    "host_path_ms_per_batch.audited": "host_path_ms_per_batch.sat"}
NEW = set(SAME_READ) | {
    "journal_lines_ms_per_batch.audited",
    "journal_record_ms_per_batch.audited",
    "journal_write_ms_per_batch.audited",
    "audit_observe_ms_per_batch.audited",
    "audit_check_ms_per_batch.audited",
    "audit_entries_per_check.audited",
    "journal_events_per_msg.audited", "journal_bytes_per_msg.audited",
    "audit_batches_per_batch.audited", "audit_shadow_positions.audited",
    "tsdb_append_ms_per_batch.audited"}


def found_file(name):
    return run.load_json(os.path.join(layers.HERE, "layer_metrics",
                                      f"{name}.json"))


def test_serve_is_fixed_zipf_1ks_plus_the_planes():
    _traffic, config = run.load_cell(CELL)
    _t, zipf = run.load_cell("zipf1k-sat")
    assert config["name"] == CONFIG and zipf["name"] == "fixed-zipf-1k"
    assert config["serve"] == zipf["serve"] + FLAGS
    assert config["stream"] == zipf["stream"]
    assert config["guarantees"][:6] == zipf["guarantees"]
    assert len(config["guarantees"]) == 8
    assert "fsynced with its batch" in config["guarantees"][6]
    assert "every snapshot" in config["guarantees"][7]
    for key in ("reference", "control", "expect"):
        assert config[key] == zipf[key], key
    assert config["control"]["reference"]["max_fills"] == 2
    assert config["reduced"] == []
    assert config["assumed"].keys() > zipf["assumed"].keys()
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and "README.md" in entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources)


def test_kme_serve_takes_the_planes_paths_under_its_checkpoint_dir():
    from kme_tpu.bridge import serve

    args = serve.build_parser().parse_args(
        FLAGS + ["--checkpoint-dir", "/x/state"])
    assert args.journal_out.startswith(serve._CKPT_PREFIX)
    # without --checkpoint-dir the prefix has nothing to stand for
    assert serve.main(FLAGS + ["--engine", "oracle"]) == 2


def test_cell_files_load_and_its_layer_metrics_resolve():
    traffic, _config = run.load_cell(CELL)
    zipf, _c = run.load_cell("zipf1k-sat")
    assert (traffic["name"], traffic["config"]) == (CELL, CONFIG)
    assert {k: v for k, v in traffic.items()
            if k not in ("name", "config", "why")} \
        == {k: v for k, v in zipf.items()
            if k not in ("name", "config", "why")}
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
    # no file of another cell's list takes this cell up
    assert not set(SAME_READ.values()) & set(found)
    for mine, theirs in SAME_READ.items():
        assert found[mine]["read"] == found_file(theirs)["read"], mine
    spans = {s["name"]: s["target"] for s in (
        run.load_json(os.path.join(layers.HERE, "spans", f))
        for f in os.listdir(os.path.join(layers.HERE, "spans")))}
    from benchmark.host import resolve

    for name in ("journal_record", "audit_observe", "audit_check_engine"):
        assert callable(resolve(spans[name])[2]), name


SMALL = {"--symbols": "32", "--accounts": "256", "--slots": "4096"}


def patched_cell():
    """The cell at a size the interpreter serves inside a window."""
    traffic, config = run.load_cell(CELL)
    for flag, value in SMALL.items():
        config["serve"][config["serve"].index(flag) + 1] = value
    config["stream"]["params"].update(num_symbols=32, num_accounts=256)
    config["reference"]["book_slots"] = 4096
    config["control"]["reference"]["book_slots"] = 4096
    traffic["warmup_messages"] = 2 * 256 + 32 + 1024
    return traffic, config


def rehearse(out, **kw):
    cell = patched_cell()
    mp = pytest.MonkeyPatch()
    mp.setattr(run, "load_cell", lambda name: cell)
    try:
        return run.run_cell(CELL, seed=2 ** 31 + 17, seconds=4,
                            allow_cpu=True, events=60000, out=str(out),
                            **kw)
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """`--allow-cpu --events 60000 --seconds 4 --trace 1`; keeps the
    heartbeats the layer metrics read."""
    kept = {}
    read = layers.read

    def keeping(spec, ctx):
        kept.update(hb_a=ctx["hb_a"], hb_b=ctx["hb_b"])
        return read(spec, ctx)

    mp = pytest.MonkeyPatch()
    mp.setattr(layers, "read", keeping)
    try:
        result = rehearse(tmp_path_factory.mktemp("run"), trace=True)
    finally:
        mp.undo()
    return result, kept


def test_rehearsal_of_the_cell(rehearsal):
    result, _hbs = rehearsal
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["rehearsal"] == "cpu"
    assert result["compared"]["heartbeat degraded"] \
        == {"value": None, "limit": None}
    assert result["compared"]["final rej_capacity"] \
        == {"value": 0, "limit": 0}
    assert set(result["metrics"]) >= {
        f"cpu_rehearsal.{n}" for n in NEW - FROM_TRACE}


def test_control_rehearsal_is_not_correct(tmp_path):
    result = rehearse(tmp_path / "run", trace=False, control=True)
    assert result["correct"] is False
    assert result["compared"]["heartbeat degraded"]["value"] is None
    assert set(result["metrics"]) == {"cpu_rehearsal.orders_per_s",
                                      "cpu_rehearsal.setup_s"}


def test_tampered_rehearsal_is_not_correct(tmp_path, monkeypatch):
    """The auditor's verdict is the cell's: a shadow that disagrees
    (here: fed one fill with a quantity one too high) marks the
    heartbeat degraded, and the judge reads that as not correct while
    MatchOut is byte-exact."""
    monkeypatch.setenv("KME_AUDIT_TAMPER", "fill_qty")
    result = rehearse(tmp_path / "run", trace=False)
    assert result["correct"] is False
    # (a rehearsal's backend is waived, not equal to its limit)
    missed = {what for what, c in result["compared"].items()
              if c["value"] != c["limit"] and what != "backend, interpret"}
    assert missed == {"heartbeat degraded"}, result["compared"]


@pytest.mark.parametrize("name", sorted(NEW - FROM_TRACE))
def test_new_metric_reads_the_rehearsals_heartbeats(name, rehearsal):
    result, hbs = rehearsal
    spec = found_file(name)
    value = layers.read(spec["read"], hbs)
    assert isinstance(value, (int, float)) and value >= 0, (name, value)
    assert result["metrics"][f"cpu_rehearsal.{name}"]["value"] == value
    if name == "audit_batches_per_batch.audited":
        assert value == 1.0
    if name == "snapshot_fetch_calls.audited":
        assert value >= 1       # by live rows (the chip: 1 call)
    if name == "journal_bytes_per_msg.audited":
        per_msg = layers.read(found_file(
            "journal_events_per_msg.audited")["read"], hbs)
        assert value == pytest.approx(96 * per_msg)
        assert 3 < per_msg < 8
    if name == "audit_entries_per_check.audited":
        assert value > 256 + 32
    # and nothing, without raising, from a program without them
    bare = {k: dict(hb, metrics={"counters": {
        "service_batches": hb["metrics"]["counters"]["service_batches"],
        "service_records": hb["metrics"]["counters"]["service_records"]},
        "gauges": {}}) for k, hb in hbs.items()}
    assert layers.read(spec["read"], bare) is None
