"""Perf-regression gate: metric extraction from recorded (truncated)
benchmark artifacts, direction-aware comparison, and the kme-bench
--gate exit-code contract CI depends on."""

import json

import pytest

from kme_tpu import perfgate
from kme_tpu.benchmarks import main as bench_main

# a driver-format artifact whose tail starts MID-OBJECT, the way
# recorded driver artifacts are truncated; the java sub-dict
# repeats metric names and must NOT shadow the root values
_TAIL = (
    '_ms": 1.23, "local_orders_per_sec": 100000.0, '
    '"engine_side_p50_ms": 2.0, "engine_side_p99_ms": 4.0, '
    '"device_ms_per_batch": 5.0, "backend": "cpu", '
    '"pipeline_speedup": 1.4, '
    '"java": {"local_orders_per_sec": 5000.0, "engine_side_p99_ms": 99.0}'
)


def _artifact(tmp_path, name="base.json", tail=_TAIL):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        json.dump({"n": 5, "cmd": ["kme-bench"], "rc": 0,
                   "tail": tail, "parsed": None}, f)
    return p


def test_extract_metrics_truncated_first_wins():
    m = perfgate.extract_metrics(_TAIL)
    assert m["local_orders_per_sec"] == 100000.0     # root, not java's
    assert m["engine_side_p99_ms"] == 4.0
    assert perfgate.extract_backend(_TAIL) == "cpu"
    # scientific notation and negatives parse
    m2 = perfgate.extract_metrics('"p99_ms": 1.5e-2, "x": -3')
    assert m2["p99_ms"] == pytest.approx(0.015) and m2["x"] == -3


def test_load_artifact_shapes(tmp_path):
    art = perfgate.load_artifact(_artifact(tmp_path))
    assert art["source"] == "driver-tail"
    assert art["metrics"]["device_ms_per_batch"] == 5.0
    # plain detail JSON and raw text both load
    pj = str(tmp_path / "detail.json")
    with open(pj, "w") as f:
        json.dump({"p99_ms": 3.0, "backend": "tpu"}, f)
    art2 = perfgate.load_artifact(pj)
    assert art2["source"] == "json" and art2["backend"] == "tpu"
    pt = str(tmp_path / "raw.txt")
    with open(pt, "w") as f:
        f.write('garbage then "p50_ms": 7 more garbage')
    assert perfgate.load_artifact(pt)["metrics"]["p50_ms"] == 7.0


def test_compare_direction_aware():
    base = {"metrics": {"local_orders_per_sec": 100.0, "p99_ms": 10.0},
            "backend": "cpu"}
    # throughput UP and latency DOWN are both improvements
    good = {"metrics": {"local_orders_per_sec": 150.0, "p99_ms": 5.0},
            "backend": "cpu"}
    rep = perfgate.compare(base, good, tolerance=0.25)
    assert rep["ok"] and rep["regressions"] == []
    # throughput falling 2x regresses; latency rising 2x regresses
    bad = {"metrics": {"local_orders_per_sec": 50.0, "p99_ms": 20.0},
           "backend": "cpu"}
    rep = perfgate.compare(base, bad, tolerance=0.25)
    assert not rep["ok"]
    assert set(rep["regressions"]) == {"local_orders_per_sec", "p99_ms"}
    # inside tolerance is clean
    meh = {"metrics": {"local_orders_per_sec": 90.0, "p99_ms": 11.0},
           "backend": "cpu"}
    assert perfgate.compare(base, meh, tolerance=0.25)["ok"]


def test_compare_backend_mismatch_is_advisory():
    base = {"metrics": {"p99_ms": 10.0}, "backend": "tpu"}
    bad = {"metrics": {"p99_ms": 100.0}, "backend": "cpu"}
    rep = perfgate.compare(base, bad)
    assert rep["backend_mismatch"] and rep["advisory"]
    assert rep["regressions"] == ["p99_ms"]   # reported...
    assert rep["ok"]                          # ...but not enforced
    assert "ADVISORY" in perfgate.format_report(rep)


def test_compare_advisory_metrics_never_regress():
    base = {"metrics": {"pipeline_speedup": 2.0, "p99_ms": 1.0},
            "backend": "cpu"}
    cur = {"metrics": {"pipeline_speedup": 0.5, "p99_ms": 1.0},
           "backend": "cpu"}
    rep = perfgate.compare(base, cur)
    assert rep["ok"] and rep["regressions"] == []
    row = [r for r in rep["metrics"] if r["name"] == "pipeline_speedup"]
    assert row and row[0]["status"] == "advisory"


def test_gate_cli_exit_codes(tmp_path, capsys):
    base = _artifact(tmp_path, "base.json")
    # self-compare: clean, exit 0
    rc = bench_main(["--baseline", base, "--gate",
                     "--gate-current", base])
    assert rc == 0
    assert "gate clean" in capsys.readouterr().err
    # doctored 2x slowdown: exit 1 with the regression named
    slow = _artifact(tmp_path, "slow.json", tail=_TAIL
                     .replace('"local_orders_per_sec": 100000.0',
                              '"local_orders_per_sec": 50000.0')
                     .replace('"engine_side_p99_ms": 4.0',
                              '"engine_side_p99_ms": 8.0'))
    report = str(tmp_path / "report.json")
    rc = bench_main(["--baseline", base, "--gate", "--gate-current",
                     slow, "--tolerance", "0.25",
                     "--gate-report", report])
    assert rc == 1
    err = capsys.readouterr().err
    assert "REGRESSION" in err and "engine_side_p99_ms" in err
    rep = json.loads(open(report).read())
    assert "local_orders_per_sec" in rep["regressions"]
    # backend mismatch (cpu current vs tpu-flagged baseline): advisory 0
    tpu_base = _artifact(tmp_path, "tpu.json",
                         tail=_TAIL.replace('"backend": "cpu"',
                                            '"backend": "tpu"'))
    rc = bench_main(["--baseline", tpu_base, "--gate",
                     "--gate-current", slow])
    assert rc == 0
    assert "ADVISORY" in capsys.readouterr().err


def test_gate_cli_unusable_baseline_exits_2(tmp_path, capsys):
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as f:
        f.write("no numbers here")
    base = _artifact(tmp_path)
    # metric-less BASELINE → 2
    rc = bench_main(["--baseline", empty, "--gate",
                     "--gate-current", base])
    assert rc == 2
    # metric-less CURRENT → 2 as well
    rc = bench_main(["--baseline", base, "--gate",
                     "--gate-current", empty])
    assert rc == 2
    capsys.readouterr()


def test_gate_requires_baseline():
    with pytest.raises(SystemExit):
        bench_main(["--gate"])


def test_publish_pipeline_gauges():
    from kme_tpu.benchmarks import publish_pipeline_gauges
    from kme_tpu.telemetry import Registry

    reg = Registry()
    publish_pipeline_gauges(reg, {
        "pipeline_speedup": 0.8, "device_ms_per_batch": 3.5,
        "measured_overlap_frac": 0.4, "pipeline_warning": "slow"})
    g = reg.snapshot()["gauges"]
    assert g["pipeline_speedup"] == 0.8
    assert g["device_ms_per_batch"] == 3.5
    assert g["measured_overlap_frac"] == 0.4
    assert g["pipeline_warning"] == 1
    publish_pipeline_gauges(reg, {"pipeline_speedup": 1.6})
    assert reg.snapshot()["gauges"]["pipeline_warning"] == 0
