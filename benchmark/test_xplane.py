"""The trace reduction on a small recorded trace: the first 12 s of a
`zipf1k-sat` traced window on a TPU v5 lite (my chip run, PR 24), cut to
the device plane's `XLA Modules` / `XLA Ops` lines and the host plane's
TraceAnnotation spans. The expected numbers were computed from the same
file with an independent reader (tensorflow's xplane_pb2)."""

import os

import pytest

from benchmark import xplane

TRACE = os.path.join(os.path.dirname(__file__), "testdata",
                     "zipf1k-sat.xplane.pb")
SPANS = ["checkpoint", "process_batch", "produce_buffer", "publish_batch",
         "session_collect", "session_metrics", "session_submit"]


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(xplane.load(TRACE), SPANS)


def test_fixture_is_small():
    assert os.path.getsize(TRACE) < 200_000


def test_busy_and_window(reduced):
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(0.0835607, rel=1e-4)
    assert reduced["window_s"] == pytest.approx(12.9978, rel=1e-5)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_scan_program(reduced):
    (name, rec), = [(k, v) for k, v in reduced["programs"].items()
                    if k.startswith("jit_call_scan")]
    assert rec["runs"] == 6
    assert rec["seconds"] == pytest.approx(0.0835520, rel=1e-4)


def test_host_spans_and_gaps(reduced):
    assert reduced["span_s"]["session_metrics"] == pytest.approx(8.43431,
                                                                 rel=1e-5)
    assert reduced["span_s"]["checkpoint"] == pytest.approx(2.52661,
                                                            rel=1e-5)
    assert reduced["span_s"]["process_batch"] == 0
    gaps = dict(reduced["idle_gaps"])
    # metrics() runs inside _publish_batch: the gap goes to the inner span
    assert "publish_batch" not in gaps
    assert gaps["session_metrics"] > gaps["checkpoint"] > 1.0
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert len(reduced["device_ops"]) <= 10


def test_no_device_operation_is_an_error():
    class Event:
        name, start_ns, duration_ns = "x", 0.0, 5.0

    class Line:
        name, events = "python", [Event()]

    class Plane:
        name, lines = "/host:CPU", [Line()]

    class Profile:
        planes = [Plane()]

    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce(Profile())


def test_interval_helpers():
    assert xplane.merge([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert xplane.overlap(1, 5.5, [[0, 3], [5, 6]]) == pytest.approx(2.5)
    assert xplane.short_op(
        "%while.2 = (u32[]{:T(128)}, s32[1,128]{1,0}) while(%x), body=%b"
    ) == "%while.2 while"
