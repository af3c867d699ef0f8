"""The fault-injection registry (kme_tpu/faults.py) and the hardening
it exists to attack: spec parsing, seed determinism, cross-process fire
accounting, file damage helpers, the broker's bounded-ingress shed and
the service's produce retry-with-backoff."""

import os
import random

import pytest

from kme_tpu import faults
from kme_tpu.bridge.broker import (BrokerError, BrokerOverload,
                                   InProcessBroker)
from kme_tpu.bridge.provision import provision
from kme_tpu.bridge.service import TOPIC_IN, TOPIC_OUT, MatchService
from kme_tpu.faults import FaultPlan, FaultSpecError
from kme_tpu.wire import dumps_order
from kme_tpu.workload import harness_stream


@pytest.fixture(autouse=True)
def _clean_registry():
    """The module-level plan is process state: never leak it."""
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# spec grammar


def test_spec_parses_points_and_fields():
    plan = FaultPlan("seed=7;broker.fetch:n=2;ckpt.torn:frac=0.25:after=1;"
                     "serve.kill:at=500;tcp.partial:p=0.5:n=0")
    assert plan.seed == 7
    assert [r.point for r in plan.rules] == [
        "broker.fetch", "ckpt.torn", "serve.kill", "tcp.partial"]
    assert plan.rules[0].n == 2
    assert plan.rules[1].frac == 0.25 and plan.rules[1].after == 1
    assert plan.rules[2].at == 500
    assert plan.rules[3].p == 0.5 and plan.rules[3].n == 0


def test_spec_rejects_unknown_point_and_bad_fields():
    with pytest.raises(FaultSpecError, match="unknown fault point"):
        FaultPlan("broker.explode")
    with pytest.raises(FaultSpecError, match="unknown fault field"):
        FaultPlan("broker.fetch:whatever=1")
    with pytest.raises(FaultSpecError, match="key=value"):
        FaultPlan("broker.fetch:n")


def test_default_rule_fires_exactly_once():
    plan = FaultPlan("broker.fetch")
    assert plan.fire("broker.fetch") is not None
    assert all(plan.fire("broker.fetch") is None for _ in range(10))
    assert plan.fired_total() == 1


def test_n_zero_is_unlimited_and_after_skips():
    plan = FaultPlan("broker.fetch:n=0:after=2")
    got = [plan.fire("broker.fetch") is not None for _ in range(6)]
    assert got == [False, False, True, True, True, True]


def test_at_gates_on_offset():
    plan = FaultPlan("serve.kill:at=100")
    assert plan.fire("serve.kill", offset=50) is None
    assert plan.fire("serve.kill", offset=None) is None
    assert plan.fire("serve.kill", offset=100) is not None
    assert plan.fire("serve.kill", offset=200) is None  # n=1 spent


def test_probability_is_seed_deterministic():
    def draws(seed):
        plan = FaultPlan(f"seed={seed};broker.fetch:p=0.5:n=0")
        return [plan.fire("broker.fetch") is not None for _ in range(40)]

    a, b = draws(3), draws(3)
    assert a == b                     # same seed, same decisions
    assert any(a) and not all(a)      # actually probabilistic
    assert draws(4) != a              # a different seed diverges


def test_state_dir_makes_n_global_across_plans(tmp_path):
    """A restarted child re-parses the same spec; the state dir must
    keep an n=1 rule from refiring in the new incarnation."""
    sd = str(tmp_path)
    p1 = FaultPlan("broker.fetch:n=2", state_dir=sd)
    assert p1.fire("broker.fetch") is not None
    # "restart": a fresh plan (fresh in-process counters), same state dir
    p2 = FaultPlan("broker.fetch:n=2", state_dir=sd)
    assert p2.fire("broker.fetch") is not None   # fire 2 of 2
    p3 = FaultPlan("broker.fetch:n=2", state_dir=sd)
    assert p3.fire("broker.fetch") is None       # budget spent globally


def test_damage_file_torn_and_bitflip(tmp_path):
    blob = bytes(range(256)) * 4
    torn = tmp_path / "torn.bin"
    torn.write_bytes(blob)
    faults.configure("ckpt.torn:frac=0.25")
    assert faults.damage_file("ckpt.torn", str(torn))
    assert len(torn.read_bytes()) == len(blob) // 4
    assert torn.read_bytes() == blob[:len(blob) // 4]

    flip = tmp_path / "flip.bin"
    flip.write_bytes(blob)
    faults.configure("ckpt.bitflip")
    assert faults.damage_file("ckpt.bitflip", str(flip))
    damaged = flip.read_bytes()
    assert len(damaged) == len(blob)
    diff = [i for i in range(len(blob)) if damaged[i] != blob[i]]
    assert len(diff) == 1             # exactly one byte, one bit
    assert bin(damaged[diff[0]] ^ blob[diff[0]]).count("1") == 1


def test_module_level_should_inactive_without_spec():
    assert not faults.active()
    assert not faults.should("broker.fetch")
    assert faults.fired_total() == 0


# ---------------------------------------------------------------------------
# injection points in the broker + the service's retry/backoff


def test_broker_injection_points_raise():
    faults.configure("broker.produce:n=1;broker.fetch:n=1")
    b = InProcessBroker()
    provision(b)
    with pytest.raises(BrokerError, match="injected fault"):
        b.produce(TOPIC_IN, None, "x")
    assert b.produce(TOPIC_IN, None, "x") == 0    # n=1 spent
    with pytest.raises(BrokerError, match="injected fault"):
        b.fetch(TOPIC_IN, 0)
    assert [r.value for r in b.fetch(TOPIC_IN, 0)] == ["x"]


def test_bounded_ingress_sheds_with_rej_overload():
    """max_lag arms per-topic once a consumer commits a watermark:
    produces past the bound shed with a wire-level rej_overload instead
    of growing the backlog; commits re-open the window."""
    b = InProcessBroker(max_lag=2)
    provision(b)
    # no watermark committed yet: the bound is not armed
    for i in range(4):
        b.produce(TOPIC_IN, None, f"m{i}")
    b.commit(TOPIC_IN, 0)        # consumer at 0, backlog 4 >= 2: full
    with pytest.raises(BrokerOverload) as ei:
        b.produce(TOPIC_IN, None, "m4")
    assert ei.value.code == "rej_overload"
    assert b.overload_rejects == 1
    b.commit(TOPIC_IN, 3)        # backlog 1 < 2: open again
    assert b.produce(TOPIC_IN, None, "m4") == 4
    # MatchOut has no watermark: never shed
    for i in range(10):
        b.produce(TOPIC_OUT, "OUT", f"o{i}")
    with pytest.raises(BrokerError):
        b.commit("NoSuchTopic", 0)


def test_service_produce_retry_rides_through_transient_faults():
    """Two injected produce failures mid-batch must not kill the serve
    loop: the retry path backs off, re-produces, and the output stream
    completes byte-exactly; retries surface in telemetry."""
    msgs = harness_stream(40, seed=5, num_accounts=4, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    from kme_tpu.oracle import OracleEngine

    ora = OracleEngine("fixed", book_slots=64, max_fills=32)
    want = [rec.wire() for m in msgs for rec in ora.process(m.copy())]

    b = InProcessBroker()
    provision(b)
    for m in msgs:
        b.produce(TOPIC_IN, None, dumps_order(m))
    # configure AFTER seeding so the input produces are not attacked;
    # skip the first 3 MatchOut produces, then fail twice
    faults.configure("broker.produce:n=2:after=3")
    svc = MatchService(b, engine="oracle", compat="fixed", batch=16,
                       slots=64, max_fills=32)
    assert svc.run(max_messages=len(msgs)) == len(msgs)
    got = [f"{r.key} {r.value}" for r in b.fetch(TOPIC_OUT, 0, 10 ** 6)]
    assert got == want
    snap = svc.telemetry.snapshot()
    assert snap["counters"]["broker_retries"] == 2
    assert snap["gauges"]["faults_injected"] == 2


def test_checkpoint_post_write_faults_then_fallback(tmp_path):
    """ckpt.torn / ckpt.bitflip attack the snapshot that was just made
    durable; the load path must fall back to the previous one."""
    from kme_tpu.oracle import OracleEngine
    from kme_tpu.runtime import checkpoint as ck

    ora = OracleEngine("fixed", book_slots=64, max_fills=32)
    msgs = harness_stream(60, seed=11, num_accounts=4, num_symbols=2,
                          payout_opcode_bug=False, validate=True)
    for m in msgs[:20]:
        ora.process(m)
    ck.save_oracle(str(tmp_path), ora, 20)
    faults.configure("ckpt.torn:n=1")          # tear the NEXT save
    for m in msgs[20:40]:
        ora.process(m)
    ck.save_oracle(str(tmp_path), ora, 40)
    loaded, offset = ck.load_oracle(str(tmp_path))
    assert offset == 20 and loaded is not None  # fell back past the tear


def test_exactly_once_fault_points_parse_and_fire():
    """The robustness-drill points behind the exactly-once machinery:
    lease.steal (split-brain: a rival takes the next epoch before our
    checkpoint) and standby.lag (the follower stalls mid-tail)."""
    plan = FaultPlan("seed=1;lease.steal:n=1;standby.lag:at=64")
    assert plan.fire("lease.steal") is not None
    assert plan.fire("lease.steal") is None        # n=1 spent
    assert plan.fire("standby.lag", offset=32) is None
    assert plan.fire("standby.lag", offset=64) is not None
    assert plan.fire("standby.lag", offset=128) is None

    faults.configure("lease.steal:n=1")            # module registry too
    assert faults.should("lease.steal")
    assert not faults.should("lease.steal")


# ---------------------------------------------------------------------------
# a seq service's snapshots are made on the snapshot writer's thread
# (PR 53): what fails or is attacked there is seen on the serve thread


def _seq_service(tmp_path, n=384, every=128, **more):
    """A broker holding `n` messages and a serial fixed-mode seq
    service over it that snapshots every `every` (batches of 64)."""
    from kme_tpu.workload import zipf_symbol_stream

    broker = InProcessBroker(persist_dir=str(tmp_path / "log"))
    provision(broker)
    for m in zipf_symbol_stream(n, num_symbols=8, num_accounts=24, seed=17,
                                zipf_a=1.0):
        broker.produce(TOPIC_IN, None, dumps_order(m))
    kw = dict(engine="seq", compat="fixed", symbols=8, accounts=128,
              slots=128, max_fills=16, batch=64, pipeline=0,
              checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=every,
              checkpoint_keep=100, **more)
    return broker, kw


def _offsets(kw):
    from kme_tpu.runtime import checkpoint as ck

    return sorted(off for off, _ in ck.list_snapshots(kw["checkpoint_dir"]))


def _to_the_handoff(svc):
    """Step the loop until a snapshot has been handed to its writer."""
    while svc._snap_writer is None:
        assert svc.step(timeout=0.0) > 0


@pytest.mark.parametrize("where", ["next-boundary", "close"])
def test_a_snapshot_writers_oserror_reaches_the_serve_thread(
        where, tmp_path, monkeypatch):
    """A disk that fills under the writer is no silent gap in the
    snapshots: the error is raised where the save on the serve thread
    would have raised it, one boundary later, or at close()."""
    import errno

    from kme_tpu.runtime import checkpoint as ck

    def full(ckpt_dir, offset, payload, keep=None):
        raise OSError(errno.ENOSPC, "No space left on device")

    monkeypatch.setattr(ck, "_atomic_savez", full)
    broker, kw = _seq_service(tmp_path)
    svc = MatchService(broker, **kw)
    _to_the_handoff(svc)
    assert svc.offset == 128
    with pytest.raises(OSError) as e:
        if where == "close":
            svc.close()
        else:
            svc.run(max_messages=256)
    assert e.value.errno == errno.ENOSPC
    # raised once, by whoever took the writer back
    assert svc._snap_writer is None or svc.offset == 256
    assert _offsets(kw) == []
    if where == "close":
        # the error did not keep the rest of close() from running
        assert svc.events is not None and svc.events._f is None
    svc.close()


def test_a_fence_at_a_boundary_leaves_no_writer_that_renames_later(
        tmp_path, monkeypatch):
    """lease.steal at the second boundary: the first boundary's file is
    still being written when the loop gets there. The serve thread
    takes that writer back BEFORE it reads the epoch, so when it dies
    fenced nothing is in flight: the directory holds the first file and
    stays as it is."""
    import threading
    import time

    from kme_tpu.bridge.broker import BrokerFenced
    from kme_tpu.runtime import checkpoint as ck

    savez, order = ck._atomic_savez, []

    def slow(ckpt_dir, offset, payload, keep=None):
        time.sleep(0.3)
        path = savez(ckpt_dir, offset, payload, keep=keep)
        order.append(("renamed", offset))
        return path

    monkeypatch.setattr(ck, "_atomic_savez", slow)
    broker, kw = _seq_service(tmp_path, exactly_once=True)
    svc = MatchService(broker, **kw)
    faults.configure("lease.steal:after=1")
    with pytest.raises(BrokerFenced, match="superseded"):
        try:
            svc.run(max_messages=384)
        finally:
            order.append(("raised", svc.offset))
    assert order == [("renamed", 128), ("raised", 256)]
    assert svc._snap_writer is None
    assert not [t for t in threading.enumerate()
                if t.name == "kme-snapshot-writer"]
    held = sorted(os.listdir(kw["checkpoint_dir"]))
    assert _offsets(kw) == [128] and not [f for f in held
                                          if f.endswith(".tmp")]
    time.sleep(0.5)
    assert sorted(os.listdir(kw["checkpoint_dir"])) == held
    svc.close()
    assert sorted(os.listdir(kw["checkpoint_dir"])) == held


@pytest.mark.parametrize("damage", ["ckpt.torn", "ckpt.bitflip"])
def test_post_write_faults_fire_on_the_writers_file_and_the_loader_falls_back(
        damage, tmp_path):
    """The injection points sit where the rename is, on the writer's
    thread now: the second file is damaged once durable, the loop
    serves on, and the next leader resumes from the first."""
    broker, kw = _seq_service(tmp_path)
    faults.configure(f"{damage}:after=1")
    svc = MatchService(broker, **kw)
    assert svc.run(max_messages=320) == 320
    assert faults.fired_total() == 1
    assert _offsets(kw) == [128, 256]
    del svc
    svc2 = MatchService(InProcessBroker(persist_dir=str(tmp_path / "log")),
                        **kw)
    assert svc2.offset == 128
    svc2.close()
