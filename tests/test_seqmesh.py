"""Multi-chip SEQ fleet (parallel/seqmesh.py): bit-exactness of the
symbol-sharded seq kernels + psum balance merges vs the scalar oracle
and the single-chip SeqSession, at shards 1/2/8 on the virtual mesh.

Reference analog: partitioned scale-out, topic.js:18 +
KProcessor.java:59-60 (Streams instances splitting partitions of one
topic), with sequential consistency preserved by the account-disjoint
window plan instead of single-instance serialization.
"""

import numpy as np
import pytest

from kme_tpu.engine import seq as SQ
from kme_tpu.oracle import OracleEngine
from kme_tpu.parallel.seqmesh import SeqMeshSession, shard_proof
from kme_tpu.runtime.seqsession import SeqSession
from kme_tpu.workload import zipf_symbol_stream

CFG = dict(lanes=8, slots=128, accounts=128, max_fills=16,
           pos_cap=1 << 10, probe_max=8)


def _stream(n=900, seed=11):
    return zipf_symbol_stream(n, num_symbols=8, num_accounts=24,
                              seed=seed, zipf_a=1.0, payout_per_mille=5)


def _oracle_lines(msgs):
    ora = OracleEngine("fixed", book_slots=CFG["slots"],
                       max_fills=CFG["max_fills"])
    return [r.wire() for m in msgs for r in ora.process(m.copy())]


# shards=2 is the tier-1 representative (it exercises the cross-shard
# halo path at a quarter of the cost); 1 and 8 ride in the slow lane
@pytest.mark.parametrize("shards", [
    pytest.param(1, marks=pytest.mark.slow),
    2,
    pytest.param(8, marks=pytest.mark.slow),
])
def test_seqmesh_oracle_exact(cpu_devices, shards):
    """Full wire stream bit-exact vs the scalar oracle at every shard
    count — mixed trades/cancels/transfers and true PAYOUT barriers."""
    msgs = _stream()
    ses = SeqMeshSession(SQ.SeqConfig(**CFG), shards=shards)
    got = [ln for per in ses.process_wire(msgs) for ln in per]
    assert got == _oracle_lines(msgs), f"shards={shards} diverged"


def test_seqmesh_matches_single_chip(cpu_devices):
    """The sharded session's wire stream equals the single-chip
    SeqSession's byte for byte (same engine, same stream)."""
    msgs = _stream(n=700, seed=23)
    mesh = SeqMeshSession(SQ.SeqConfig(**CFG), shards=8)
    single = SeqSession(SQ.SeqConfig(**CFG))
    got = mesh.process_wire(msgs)
    want = single.process_wire(msgs)
    assert got == want


def test_seqmesh_window_invariant(cpu_devices):
    """plan_windows: within every window an account appears on at most
    one shard, and barriers sit alone."""
    msgs = _stream(n=1200, seed=5)
    ses = SeqMeshSession(SQ.SeqConfig(**CFG), shards=8)
    cols, _ = ses.router.route(msgs)
    wins, placements, cnts, K = ses.plan_windows(cols)
    acts = cols["act"]
    barrier = {int(k) for k in range(len(acts))
               if acts[k] in (SQ.L_PAYOUT_YES, SQ.L_PAYOUT_NO,
                              SQ.L_REMOVE_SYMBOL)}
    by_window = {}
    for k, w, s, p in placements:
        by_window.setdefault(w, []).append((k, s))
    n_placed = sum(len(v) for v in by_window.values())
    assert n_placed == len(acts)
    binds = (SQ.L_BUY, SQ.L_SELL, SQ.L_CANCEL, SQ.L_CREATE,
             SQ.L_TRANSFER)
    for w, entries in by_window.items():
        ks = [k for k, _ in entries]
        if any(k in barrier for k in ks):
            assert len(ks) == 1, "barrier must run alone"
        seen = {}
        for k, s in entries:
            if int(acts[k]) in binds:
                a = int(cols["aid"][k])
                assert seen.setdefault(a, s) == s, \
                    f"account {a} on two shards in window {w}"


def test_shard_proof_cpu(cpu_devices):
    """The proof chip_smoke.py --chips 4 runs on the chips, here at
    shards 1/2 on a short stream: it raises on any divergence, a
    placement that never migrated, shard states sharing a device or a
    lockstep leg that differs."""
    proof = shard_proof(600, (1, 2))
    assert proof["shard_counts"] == [1, 2]
    assert proof["migrations"][1] > 0
