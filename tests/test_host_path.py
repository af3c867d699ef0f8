"""The native host path (plan/recon in C++) and the double-buffered
service pipeline.

What must hold (ISSUE r06 acceptance):

- kme_plan_batch packs the exact (cols, host_rejects, stacked, cnts, K)
  the Python route+pack produces — plane for plane;
- a pipelined MatchService (--pipeline N) emits a byte-identical
  MatchOut stream to serial serving, with every durability contract
  intact (checkpoints land at the same offsets, crash-resume replays
  the same tail);
- the serve loop publishes the host-path attribution gauges
  (plan_s / recon_s / host_path_s, pipeline_depth when pipelined).
"""

import numpy as np
import pytest

from kme_tpu.bridge.broker import InProcessBroker
from kme_tpu.bridge.consume import consume_lines
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, MatchService
from kme_tpu.engine import seq as SQ
from kme_tpu.native import load_library
from kme_tpu.wire import WireBatch, dumps_order
from kme_tpu.workload import harness_stream

needs_native = pytest.mark.skipif(
    load_library() is None,
    reason="native host runtime unavailable (KME_NATIVE=0 or no "
           "toolchain); pipelined serving gates on it")


def _pump(broker, msgs):
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))


_SEQ_KW = dict(engine="seq", compat="fixed", batch=128, symbols=8,
               accounts=128, slots=128, max_fills=32)


@needs_native
def test_plan_batch_parity_native_vs_python():
    """kme_plan_batch (one native call: envelope + route + pack) vs the
    numpy fallback pack over the same router: identical columnar rows,
    reject set, stacked scan planes, chunk counts."""
    from kme_tpu.runtime.seqsession import SeqSession

    cfg = SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=32,
                       batch=128, pos_cap=1 << 11, fill_cap=1 << 12,
                       probe_max=16)
    ses_a, ses_b = SeqSession(cfg), SeqSession(cfg)
    msgs = harness_stream(300, seed=9, num_symbols=4, num_accounts=8,
                          payout_opcode_bug=False, validate=True)
    for lo in range(0, 256, 128):
        wb = WireBatch.from_msgs(msgs[lo:lo + 128])
        cols_a, rej_a, stk_a, cnts_a, K_a = ses_a._plan(wb)
        # a plain list skips the isinstance(WireBatch) fast path, so
        # ses_b routes + packs in Python over the same messages
        cols_b, rej_b, stk_b, cnts_b, K_b = ses_b._plan(list(wb.msgs()))
        assert (K_a, cnts_a, rej_a) == (K_b, cnts_b, rej_b)
        assert set(cols_a) == set(cols_b)
        for f in cols_a:
            assert np.array_equal(cols_a[f], cols_b[f]), f"cols[{f!r}]"
        assert set(stk_a) == set(stk_b)
        for f in stk_a:
            assert np.array_equal(np.asarray(stk_a[f]),
                                  np.asarray(stk_b[f])), f"stacked[{f!r}]"


@needs_native
def test_pipelined_service_byte_parity_and_gauges():
    """Serial (--pipeline 0) vs double-buffered (--pipeline 2) serving
    over the same stream: byte-identical MatchOut, and the pipelined
    loop publishes the host-path attribution gauges."""
    msgs = harness_stream(600, seed=3)
    outs = []
    for pipeline in (0, 2):
        broker = InProcessBroker()
        provision(broker)
        _pump(broker, msgs)
        svc = MatchService(broker, pipeline=pipeline, **_SEQ_KW)
        assert svc.run(max_messages=len(msgs)) == len(msgs)
        if pipeline:
            g = svc.telemetry.snapshot()["gauges"]
            for name in ("plan_s", "recon_s", "host_path_s",
                         "pipeline_depth"):
                assert name in g, name
            # host_path_s is round(plan+recon, 6) while the addends are
            # rounded separately — the two roundings can disagree by up
            # to 1.5e-6, so the tolerance must sit above that
            assert g["host_path_s"] == pytest.approx(
                g["plan_s"] + g["recon_s"], abs=2e-6)
            assert g["pipeline_depth"] == 0  # drained at run() exit
        svc.close()
        outs.append(list(consume_lines(broker, follow=False)))
    assert outs[0] == outs[1]
    assert len(outs[0]) > 0


@needs_native
def test_pipelined_checkpoint_crash_resume(tmp_path):
    """Crash-resume with batches in flight: checkpoints must land at
    the same offsets as serial serving (offsets only advance at collect,
    and the cadence pre-drains the pipe), so a crash past the last
    snapshot replays the identical at-least-once tail."""
    msgs = harness_stream(600, seed=3)  # 623 messages
    outs = []
    for pipeline in (0, 2):
        broker = InProcessBroker()
        provision(broker)
        _pump(broker, msgs)
        ck = str(tmp_path / f"ck{pipeline}")
        kw = dict(checkpoint_dir=ck, checkpoint_every=300,
                  pipeline=pipeline, **_SEQ_KW)
        svc = MatchService(broker, **kw)
        # batches of 128: snapshot fires at offset 384; crash at 512
        assert svc.run(max_messages=512) == 512
        assert svc._last_ckpt_offset == 384
        assert svc.offset == 512
        del svc  # crash: 128 records past the snapshot
        svc2 = MatchService(broker, **kw)
        assert svc2.offset == 384  # resumed from the snapshot
        rest = len(msgs) - 384
        assert svc2.run(max_messages=rest) == rest
        svc2.close()
        outs.append(list(consume_lines(broker, follow=False)))
    # serial crash-resume is the established-correct reference
    # (test_checkpoint.py); pipelined must replay the exact same tail
    assert outs[0] == outs[1]


def test_host_gauges_published_on_serial_path():
    """plan_s/recon_s/host_path_s come from the session's phase timer,
    so the serial seq path (and the KME_NATIVE=0 fallback) publishes
    them too — the attribution surface does not gate on the pipeline."""
    msgs = harness_stream(300, seed=5)
    broker = InProcessBroker()
    provision(broker)
    _pump(broker, msgs)
    svc = MatchService(broker, **_SEQ_KW)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    g = svc.telemetry.snapshot()["gauges"]
    svc.close()
    for name in ("plan_s", "recon_s", "host_path_s"):
        assert name in g and g[name] >= 0.0, name
    assert "pipeline_depth" not in g  # serial run: no pipeline surface
