"""The layer metrics added by PR 46 (CPU beside wall, by span and by
thread; `lat_inflight`; `latency_stamp`), as data: each file is read
against a second recorded pair of heartbeats, of PR 46's tree, and
reads a number there; against the first pair (`test_spans.py`'s, of a
tree that has none of these gauges) it reads nothing, without raising.

`testdata/zipf1k-sat.pr46.heartbeats.json` is the pair a CPU rehearsal
of `zipf1k-sat` read, the first pair's command on this tree
(`--allow-cpu --events 60000 --seconds 22`, seed 5). Its numbers are
counts and clocks of a CPU run under the Pallas interpreter: nothing
here is a time of the device."""

import os

import pytest

from benchmark import layers
from benchmark.test_spans import HERE, RECORDED, load, metric_files

NAMES = sorted(
    [f"{n}.{kind}" for n in (
        "serve_cpu_share", "process_cpu_cores", "tcp_ingress_cpu_share",
        "tcp_egress_cpu_share", "serve_offcpu_ms_per_batch",
        "fetch_offcpu_ms_per_batch", "dispatch_cpu_ms_per_batch",
        "inflight_wait_ms") for kind in ("sat", "paced")]
    + ["host_path_offcpu_ms_per_batch.sat",
       "snapshot_export_offcpu_ms_per_batch.sat",
       "latency_stamp_ms_per_batch.sat", "loop_unnamed_ms_per_batch.sat",
       "ingress_parse_ns_per_msg.sat"])
SATURATED = ["zipf1k-sat", "lifecycle1k-sat", "zipf1k-cancel80-sat",
             "vmem-default-sat", "java-harness-sat"]
PIPELINED = SATURATED[:3]       # the serial path has neither stage
PAIR = load(os.path.join(HERE, "testdata",
                         "zipf1k-sat.pr46.heartbeats.json"))
CTX = dict(RECORDED, hb_a=PAIR["hb_a"], hb_b=PAIR["hb_b"])


def read(name, ctx=CTX):
    return layers.read(metric_files()[name]["read"], ctx)


def test_the_table_is_twenty_one_files():
    assert len(NAMES) == 21 and set(NAMES) <= set(metric_files())


@pytest.mark.parametrize("name", NAMES)
def test_file_reads_this_trees_pair_and_nothing_from_the_parents(name):
    spec = metric_files()[name]
    value = read(name)
    # fed by what the pair holds, not by the cells a file lists: a
    # `.paced` file reads the saturated rehearsal's gauges as well
    assert isinstance(value, (int, float)), (name, value)
    assert value >= 0 or name.startswith("loop_unnamed")
    assert read(name, RECORDED) is None
    if name.endswith(".paced"):
        assert (spec["cells"], spec["moves"]) == (
            ["zipf1k-paced-loaded"], "p50_ms")
    else:
        want = PIPELINED if name.startswith(
            ("inflight_wait", "host_path_offcpu")) else SATURATED
        assert (spec["cells"], spec["moves"]) == (want, "orders_per_s")
    assert spec["source"] in ("program_span", "program_counter")


def test_what_the_pair_says():
    got = {n: read(n) for n in NAMES}
    old = {n: read(n) for n in (
        "fetch_ms_per_batch.sat", "dispatch_ms_per_batch.sat",
        "snapshot_export_ms_per_batch.sat", "host_path_ms_per_batch.sat",
        "loop_other_ms_per_batch.sat", "parse_ns_per_msg.sat")}
    # a thread runs at most all of its wall; the roles are part of the
    # process (1% for the clocks' grain)
    assert 0 < got["serve_cpu_share.sat"] <= 1.001
    assert got["serve_cpu_share.sat"] + got["tcp_ingress_cpu_share.sat"] \
        + got["tcp_egress_cpu_share.sat"] \
        <= got["process_cpu_cores.sat"] * 1.01
    assert got["tcp_ingress_cpu_share.sat"] > 0
    assert got["tcp_egress_cpu_share.sat"] > 0
    # the same reads under both suffixes
    for n in NAMES:
        if n.endswith(".paced"):
            assert got[n] == got[n[:-len("paced")] + "sat"]
    # off-CPU is part of the wall, CPU is part of the wall
    assert got["fetch_offcpu_ms_per_batch.sat"] \
        <= old["fetch_ms_per_batch.sat"] + 1e-6
    assert got["dispatch_cpu_ms_per_batch.sat"] \
        <= old["dispatch_ms_per_batch.sat"] + 1.0
    assert got["snapshot_export_offcpu_ms_per_batch.sat"] \
        <= old["snapshot_export_ms_per_batch.sat"] + 1e-6
    assert got["host_path_offcpu_ms_per_batch.sat"] \
        <= old["host_path_ms_per_batch.sat"] + 1e-6
    wall = read("batch_wall_ms.sat")
    assert 0 < got["serve_offcpu_ms_per_batch.sat"] <= wall * 1.05
    # under the interpreter the scan runs inside the fetch: a wait
    assert got["fetch_offcpu_ms_per_batch.sat"] \
        > 0.5 * old["fetch_ms_per_batch.sat"]
    # the stamping is the named part of what loop_other holds
    assert 0 < got["latency_stamp_ms_per_batch.sat"] \
        <= old["loop_other_ms_per_batch.sat"]
    assert got["loop_unnamed_ms_per_batch.sat"] \
        <= old["loop_other_ms_per_batch.sat"] \
        - got["latency_stamp_ms_per_batch.sat"] + 1e-6
    # two batches in flight: an order waits about two turns of the loop
    assert wall < got["inflight_wait_ms.sat"] < 4 * wall
    assert got["ingress_parse_ns_per_msg.sat"] > 0


def test_the_pairs_own_invariants():
    """What ISSUE 46 asks of every heartbeat pair on the chip, held on
    the recorded one: a span's CPU within its wall (1 ms an entry), the
    loop's CPU within the loop's wall, the roles within the process."""
    for hb in (PAIR["hb_a"], PAIR["hb_b"]):
        g = hb["metrics"]["gauges"]
        # CPU is read for the spans a metric file reads, no others
        spans = sorted(k[:-len("_cpu_s")] for k in g
                       if k.endswith("_cpu_s") and k not in (
                           "serve_cpu_s", "process_cpu_s",
                           "tcp_ingress_cpu_s", "tcp_egress_cpu_s"))
        assert spans == ["dispatch", "fetch", "plan", "recon",
                         "snapshot_export"]
        for s in spans:
            assert 0 <= g[s + "_cpu_s"] <= g[s + "_s"] \
                + 1e-3 * max(1, g[s + "_n"]), s
        assert g["serve_cpu_s"] <= g["serve_loop_s"]
        assert g["serve_cpu_s"] + g["tcp_ingress_cpu_s"] \
            + g["tcp_egress_cpu_s"] <= g["process_cpu_s"] * 1.01
        assert "lat_inflight" in hb["metrics"]["latencies"]


def test_loop_unnamed_subtracts_the_partition_and_the_named_between():
    from kme_tpu.bridge.service import MatchService

    terms = metric_files()["loop_unnamed_ms_per_batch.sat"]["read"]["terms"]
    plus = [t["key"] for t in terms if t["sign"] == 1]
    minus = [t["key"] for t in terms if t["sign"] == -1]
    assert plus == ["gauges.serve_loop_s"]
    assert sorted(minus) == sorted(
        f"gauges.{n}_s" for n in MatchService.LOOP_SPANS
        + MatchService.BETWEEN_SPANS)
    assert "latency_stamp" in MatchService.BETWEEN_SPANS
    assert not set(MatchService.BETWEEN_SPANS) & set(
        MatchService.LOOP_SPANS + MatchService.INNER_SPANS)
    for t in terms:
        assert (t["reduce"], t["per"], t["scale"]) == (
            "delta_per", "counters.service_batches", 1000)
