"""The two layer metrics added by PR 49 (`fetch_ready_share.sat` /
`.paced`: of the batches collected, the share whose output prefix was
on the host already), as data: each file is read against a third
recorded pair of heartbeats, of PR 49's tree, and reads a share there;
against the two older pairs (trees without the counters) it reads
nothing, without raising.

`testdata/zipf1k-sat.pr49.heartbeats.json` is the pair a CPU rehearsal
of `zipf1k-sat` read, the first pair's command on this tree
(`--allow-cpu --events 60000 --seconds 22`, seed 5). Its numbers are
counts of a CPU run under the Pallas interpreter: nothing here is a
time of the device, and the share itself says nothing of a chip."""

import json
import os

import pytest

from benchmark import layers
from benchmark.test_cpu_spans import CTX as PR46
from benchmark.test_spans import HERE, RECORDED, ROOT, load, metric_files

NAMES = ["fetch_ready_share.paced", "fetch_ready_share.sat"]
PAIR = load(os.path.join(HERE, "testdata",
                         "zipf1k-sat.pr49.heartbeats.json"))
CTX = dict(RECORDED, hb_a=PAIR["hb_a"], hb_b=PAIR["hb_b"])
PIPELINED = ["zipf1k-sat", "lifecycle1k-sat", "zipf1k-cancel80-sat",
             "brokerage-tpce5k-sat"]


@pytest.mark.parametrize("name", NAMES)
def test_file_reads_a_share_with_the_counters_and_nothing_without(name):
    spec = metric_files()[name]
    a, b = (PAIR[k]["metrics"]["counters"] for k in ("hb_a", "hb_b"))
    value = layers.read(spec["read"], CTX)
    assert value == pytest.approx(
        (b["fetch_ready"] - a["fetch_ready"])
        / (b["service_batches"] - a["service_batches"]))
    assert 0 <= value <= 1
    for older in (RECORDED, PR46):
        assert "fetch_ready" not in older["hb_b"]["metrics"]["counters"]
        assert layers.read(spec["read"], older) is None
    paced = name.endswith(".paced")
    assert (spec["cells"], spec["moves"]) == (
        (["zipf1k-paced-loaded"], "p50_ms") if paced
        else (PIPELINED, "orders_per_s"))
    assert (spec["source"], spec["better"]) == ("program_counter", "higher")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == spec["cells"]
    assert entry[0]["layer"] == spec["layer"]


def test_the_pairs_counters():
    """A dispatch launches its prefix before its batch is collected, a
    batch is ready at most once, and the second round is the rare one."""
    for hb in (PAIR["hb_a"], PAIR["hb_b"]):
        c = hb["metrics"]["counters"]
        assert c["fetch_early"] >= c["service_batches"] >= c["fetch_ready"]
        assert c["fetch_early"] - c["service_batches"] <= hb["pipeline"]
        assert 0 <= c["fetch_second_rounds"] <= c["service_batches"]
