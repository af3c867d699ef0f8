"""A dispatch's output prefix is sliced and sent to the host WITH the
dispatch (SeqSession._start_fetch, inside submit / _run), and unpacked
when the batch is collected (_finish_fetch): the bytes, the overflow's
second round, the launch order, the three counters and where an engine
error surfaces. On the CPU the device queue cannot be seen, so the
launch order is read off the calls themselves."""

import pytest

import kme_tpu.opcodes as op
from kme_tpu.engine import seq as SQ
from kme_tpu.runtime.seqsession import SeqSession
from kme_tpu.wire import OrderMsg, WireBatch
from kme_tpu.workload import zipf_symbol_stream

LANES, BATCH = 16, 256
CFG = SQ.SeqConfig(lanes=LANES, slots=128, accounts=128, max_fills=32,
                   batch=BATCH, pos_cap=1 << 12, fill_cap=1 << 12,
                   probe_max=16)
# fills of the sweep batch: over the 8 groups x 128 the first hint
# fetches, under fill_cap
SWEEP_FILLS = LANES * 100


def _batches():
    """A stream as the batches it is submitted in: a preamble, 1,600
    resting one-lot sells, ONE batch whose 64 buys sweep them all
    (1,600 fills in one kernel call, where the hint in force fetches
    1,024) and which holds a host-rejected order, then mixed traffic."""
    pre = []
    for a in range(40):
        pre.append(OrderMsg(action=op.CREATE_BALANCE, aid=a))
        pre.append(OrderMsg(action=op.TRANSFER, aid=a, size=10**9))
    for s in range(LANES):
        pre.append(OrderMsg(action=op.ADD_SYMBOL, sid=s))
    oid = 1000
    makers = []
    for s in range(LANES):
        for k in range(100):
            makers.append(OrderMsg(action=op.SELL, oid=oid, aid=k % 20,
                                   sid=s, price=50, size=1))
            oid += 1
    sweep = [OrderMsg(action=op.BUY, oid=oid, aid=20, sid=999, price=55,
                      size=1)]            # no such symbol: host-rejected
    oid += 1
    for s in range(LANES):
        for k in range(4):
            sweep.append(OrderMsg(action=op.BUY, oid=oid, aid=20 + k,
                                  sid=s, price=55, size=25))
            oid += 1
    tail = zipf_symbol_stream(700, num_symbols=8, num_accounts=32, seed=49,
                              zipf_a=1.1, payout_per_mille=4)
    msgs = pre + makers
    out = [msgs[lo:lo + BATCH] for lo in range(0, len(msgs), BATCH)]
    out.append(sweep)
    out += [tail[lo:lo + BATCH] for lo in range(0, len(tail), BATCH)]
    return out


def _copies(batches):
    return [[m.copy() for m in b] for b in batches]


def _serve(ses, batches, depth, between=None):
    """submit/collect `batches` with `depth` in flight -> the bytes.
    `between(i)` runs after batch i's submit."""
    parts, pend = [], []
    for i, b in enumerate(batches):
        pend.append(ses.submit(WireBatch.from_msgs(b)))
        if between is not None:
            between(i)
        while len(pend) >= depth + 1:
            parts.append(ses.collect(pend.pop(0))[0])
    while pend:
        parts.append(ses.collect(pend.pop(0))[0])
    return b"".join(parts)


@pytest.fixture(scope="module")
def reference(cpu_devices):
    batches = _batches()
    ses = SeqSession(CFG)
    r = ses.process_wire_buffer([m for b in _copies(batches) for m in b])
    if r is None:
        pytest.skip("native toolchain unavailable")
    # one dispatch of K calls: its one early half, the sweep's overflow
    assert (ses.fetch_early, ses.fetch_second_rounds) == (1, 1)
    assert ses.fetch_ready <= 1
    return batches, r[0]


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_pipelined_bytes_and_counters(reference, depth):
    batches, want = reference
    ses = SeqSession(CFG)
    assert _serve(ses, _copies(batches), depth) == want
    # every dispatch launched its prefix; only the sweep's batch, cut by
    # a hint of 8 groups where it needed 13, took the second round (no
    # other batch overflows even the first hint, so it does not matter
    # which of them were submitted before the hint grew)
    assert ses.fetch_early == len(batches)
    assert ses.fetch_ready <= len(batches)
    assert ses.fetch_second_rounds == 1
    assert ses._ghint == -(-SWEEP_FILLS // 128)


def test_the_overflow_is_judged_by_the_hint_the_prefix_was_cut_by(reference):
    """The session's hint may have grown between a submit and its
    collect (an older batch's collect raised it): the prefix in the
    handle is still as short as it was cut."""
    batches, want = reference
    sweep_at = next(i for i, b in enumerate(batches)
                    if b[0].sid == 999)
    ses = SeqSession(CFG)

    def grow(i):
        if i == sweep_at:
            assert ses._ghint == 8
            ses._ghint = 32

    assert _serve(ses, _copies(batches), 2, between=grow) == want
    assert ses.fetch_second_rounds == 1 and ses._ghint == 32


def _record_launches(monkeypatch):
    """-> the list that takes "scan", "copy" and "block" as the session
    dispatches a scan, starts a device -> host copy, and waits."""
    import jax

    import kme_tpu.utils as U

    calls = []
    build, prefetch, block = (SQ.build_seq_scan, U.async_prefetch,
                              jax.block_until_ready)

    def build_recording(cfg, K):
        scan = build(cfg, K)

        def run(state, stacked):
            calls.append("scan")
            return scan(state, stacked)
        return run

    def prefetch_recording(values):
        calls.append("copy")
        return prefetch(values)

    def block_recording(x):
        calls.append("block")
        return block(x)

    monkeypatch.setattr(SQ, "build_seq_scan", build_recording)
    monkeypatch.setattr(U, "async_prefetch", prefetch_recording)
    monkeypatch.setattr(jax, "block_until_ready", block_recording)
    return calls


def test_a_prefix_is_on_its_way_before_the_next_scan(cpu_devices,
                                                     monkeypatch):
    msgs = zipf_symbol_stream(3 * BATCH, num_symbols=8, num_accounts=32,
                              seed=7, zipf_a=1.1)
    chunks = [msgs[lo:lo + BATCH] for lo in range(0, len(msgs), BATCH)]
    n = len(chunks)
    calls = _record_launches(monkeypatch)
    ses = SeqSession(CFG)
    handles = [ses.submit(WireBatch.from_msgs(c)) for c in _copies(chunks)]
    # submit: each scan's prefix is launched in the call that
    # dispatched it, so ahead of every later scan; nothing waits
    assert n >= 3 and calls == ["scan", "copy"] * n
    for h in handles:
        ses.collect(h)
    assert calls == ["scan", "copy"] * n      # no copy left for collect
    # _run (the serial path): behind the dispatch, before the wait
    del calls[:]
    ses = SeqSession(CFG)
    for c in _copies(chunks):
        if ses.process_wire_buffer(c) is None:
            pytest.skip("native toolchain unavailable")
    assert calls == ["scan", "copy", "block"] * n
    assert (ses.fetch_early, ses.fetch_second_rounds) == (n, 0)
    assert ses.fetch_ready <= n


def test_an_engine_error_surfaces_at_the_collect_of_its_batch(cpu_devices):
    """fill_cap 128: the sweep's first call overflows the fill buffer
    (LERR_FILLBUF_FULL). Its submit, and the submit behind it, return;
    the batch before it is collected whole; its own collect raises."""
    from kme_tpu.runtime.seqsession import LaneEngineError

    cfg = SQ.SeqConfig(lanes=LANES, slots=128, accounts=128, max_fills=32,
                       batch=BATCH, pos_cap=1 << 12, fill_cap=128,
                       probe_max=16)
    batches = _copies(_batches())
    sweep_at = next(i for i, b in enumerate(batches) if b[0].sid == 999)
    ses = SeqSession(cfg)
    for b in batches[:sweep_at - 1]:
        ses.collect(ses.submit(WireBatch.from_msgs(b)))
    before, bad, after = (ses.submit(WireBatch.from_msgs(b))
                          for b in batches[sweep_at - 1:sweep_at + 2])
    buf, line_off, msg_lines = ses.collect(before)
    assert len(msg_lines) == len(batches[sweep_at - 1]) and len(buf)
    with pytest.raises(LaneEngineError) as e:
        ses.collect(bad)
    assert e.value.code == SQ.LERR_FILLBUF_FULL
    assert ses.fetch_early == sweep_at + 2 and ses.fetch_second_rounds == 0


def test_the_serve_loop_publishes_the_fetch_counters(cpu_devices):
    """`fetch_early` / `fetch_ready` / `fetch_second_rounds` are
    heartbeat counters, set with the batch's other counters (what
    benchmark/layer_metrics/fetch_ready_share.* divide by
    `service_batches`)."""
    from kme_tpu.bridge.broker import InProcessBroker
    from kme_tpu.bridge.provision import provision
    from kme_tpu.bridge.service import TOPIC_IN, MatchService
    from kme_tpu.native import load_library
    from kme_tpu.wire import dumps_order

    if load_library() is None:
        pytest.skip("native toolchain unavailable")
    msgs = [m for b in _batches() for m in b]
    broker = InProcessBroker()
    provision(broker)
    for m in msgs:
        broker.produce(TOPIC_IN, None, dumps_order(m))
    svc = MatchService(broker, engine="seq", compat="fixed", symbols=LANES,
                       accounts=128, slots=128, max_fills=32, batch=BATCH,
                       pipeline=2)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    c = svc.telemetry.snapshot()["counters"]
    svc.close()
    assert c["fetch_early"] == c["service_batches"] == -(-len(msgs) // BATCH)
    assert 0 <= c["fetch_ready"] <= c["service_batches"]
    assert c["fetch_second_rounds"] == 1
