"""The layer metrics and spans added since PR 26, as data: every metric
file is read against a recorded pair of heartbeats of the finished
program, and those whose span or counter the recording holds (`FED`:
by what the pair holds, not by the cells a file lists) read a number,
`loop_other_ms_per_batch.sat` subtracts exactly the spans that partition
a loop iteration, every span file resolves, and every file has its
`BENCHMARK.json` entry.

`testdata/zipf1k-sat.heartbeats.json` is the pair (`hb_a` at the
window's opening, `hb_b` at its close) that a CPU rehearsal of
`zipf1k-sat` read (`--allow-cpu --events 60000 --seconds 22`, seed 5;
one snapshot inside the window). Its numbers are counts of a CPU run:
nothing here is a time of the device."""

import glob
import json
import os

import pytest

from benchmark import host, layers

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# the metric files that stood before PR 26
BEFORE = {"batch_wall_ms.paced", "batch_wall_ms.sat", "first_output_s",
          "gen_late_ms", "host_path_ms_per_batch.sat",
          "kernel_us_per_msg.paced", "kernel_us_per_msg.sat",
          "parse_ns_per_msg.sat", "produce_ms_per_batch.sat",
          "seq_kernel_roofline.sat", "unattributed_ms_per_batch.sat"}


def load(path):
    with open(path) as f:
        return json.load(f)


def metric_files():
    return {os.path.basename(p)[:-len(".json")]: load(p)
            for p in sorted(glob.glob(
                os.path.join(HERE, "layer_metrics", "*.json")))}


PAIR = load(os.path.join(HERE, "testdata", "zipf1k-sat.heartbeats.json"))
RECORDED = {"hb_a": PAIR["hb_a"], "hb_b": PAIR["hb_b"], "client": {},
            "trace": None, "config": {}, "device_kind": "cpu"}
NEW = sorted(set(metric_files()) - BEFORE)
# a metric is fed if the recorded pair holds what its `read` names:
# keyed on the recording and not on cell names, so that a file's
# `cells` list can take or lose a cell without an edit here. What the
# recording cannot feed (a later PR's counter, a trace) is read all the
# same, and must give nothing without raising
FED = [n for n, spec in metric_files().items() if n in NEW
       and layers.read(spec["read"], RECORDED) is not None]


@pytest.fixture(scope="module")
def ctx():
    return RECORDED


def test_there_are_new_metrics():
    assert len(FED) >= 21       # PR 26's; later PRs add, none takes away


@pytest.mark.parametrize("name", NEW)
def test_new_metric_reads_a_number(name, ctx):
    spec = metric_files()[name]
    assert spec["name"] == name
    value = layers.read(spec["read"], ctx)
    if name in FED:
        assert isinstance(value, (int, float)), (name, value)
        assert value >= 0 or name.startswith(("loop_other", "left_device"))
    # and nothing, without raising, from a program that has no such
    # span or counter (the parent's heartbeats)
    bare = {k: (dict(ctx[k], metrics={"counters": dict(
        ctx[k]["metrics"]["counters"]), "gauges": {}, "latencies": {}})
        if k in ("hb_a", "hb_b") else ctx[k]) for k in ctx}
    for hb in ("hb_a", "hb_b"):
        bare[hb]["metrics"]["counters"].pop("lane_switches", None)
    assert layers.read(spec["read"], bare) is None


def test_what_the_recorded_pair_says(ctx):
    read = {n: layers.read(metric_files()[n]["read"], ctx) for n in NEW}
    # a snapshot fell inside the window, and its export is part of it
    assert 0 < read["snapshot_export_ms_per_batch.sat"] \
        < read["checkpoint_ms_per_batch.sat"]
    assert read["broker_sync_ms_per_batch.sat"] \
        < read["checkpoint_ms_per_batch.sat"]
    # metrics() is its export plus its counting, inside engine_refresh
    assert read["metrics_export_ms_per_batch.sat"] \
        + read["metrics_count_ms_per_batch.sat"] \
        <= read["engine_refresh_ms_per_batch.sat"]
    # zipf(1.2) over 1,024 symbols, books in HBM
    assert 0.3 < read["lane_switches_per_msg.sat"] < 0.9
    # the partition leaves little of the loop's wall uncovered
    wall = layers.read(metric_files()["loop_other_ms_per_batch.sat"]
                       ["read"]["terms"][0], ctx)
    assert abs(read["loop_other_ms_per_batch.sat"]) < 0.05 * wall


def test_loop_other_subtracts_exactly_the_partitioning_spans():
    from kme_tpu.bridge.service import MatchService

    terms = metric_files()["loop_other_ms_per_batch.sat"]["read"]["terms"]
    plus = [t for t in terms if t["sign"] == 1]
    minus = [t for t in terms if t["sign"] == -1]
    assert [t["key"] for t in plus] == ["gauges.serve_loop_s"]
    assert sorted(t["key"] for t in minus) == sorted(
        f"gauges.{n}_s" for n in MatchService.LOOP_SPANS)
    assert len(plus) + len(minus) == len(terms)
    for t in terms:
        assert (t["reduce"], t["per"], t["scale"]) == (
            "delta_per", "counters.service_batches", 1000)


def test_every_span_file_resolves():
    names = set()
    for path in sorted(glob.glob(os.path.join(HERE, "spans", "*.json"))):
        span = load(path)
        assert os.path.basename(path) == span["name"] + ".json"
        _owner, _attr, fn = host.resolve(span["target"])
        assert callable(fn), span
        names.add(span["name"])
    # the names the program's own spans carry into the trace
    from kme_tpu.bridge.service import MatchService
    from kme_tpu.runtime.seqsession import SeqSession

    inside = set(MatchService.LOOP_SPANS + MatchService.INNER_SPANS
                 + SeqSession.SPANS)
    inside -= {"plan_s", "stage_s", "dispatch_s", "fetch_s", "recon_s"}
    assert inside <= names, sorted(inside - names)


def test_every_metric_file_has_its_benchmark_entry():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in NEW:
        spec, entry = metric_files()[name], entries[name]
        for key in ("unit", "better", "layer", "moves", "source"):
            assert spec[key] == entry[key], (name, key)
        assert spec["cells"] == entry["workloads"]
        assert set(spec["cells"]) <= cells
        # every cell that reports it reports the metric it moves
        moved = e2e[spec["moves"]]
        assert set(spec["cells"]) <= set(moved.get("workloads", cells))
    # entries were only appended: the first eleven are the old ones
    assert [m["name"] for m in bench["per_layer"]][:11] == [
        "first_output_s", "gen_late_ms", "parse_ns_per_msg.sat",
        "batch_wall_ms.sat", "batch_wall_ms.paced",
        "produce_ms_per_batch.sat", "unattributed_ms_per_batch.sat",
        "host_path_ms_per_batch.sat", "kernel_us_per_msg.sat",
        "kernel_us_per_msg.paced", "seq_kernel_roofline.sat"]
