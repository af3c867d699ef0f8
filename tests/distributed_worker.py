"""Worker process for the 2-process jax.distributed multi-host test.

Each worker owns 4 virtual CPU devices; the two workers form one
8-device mesh via jax.distributed, and the symbol-sharded seq-kernel
fleet (SeqMeshSession, parallel/seqmesh.py) runs SPMD across the
process boundary — the DCN topology of SURVEY.md §2.3
("cross-node comm backend"), validated without real hosts the idiomatic
JAX way. Usage (spawned by tests/test_multihost.py):

    python distributed_worker.py <coordinator> <nprocs> <pid> <outfile>
"""

import hashlib
import os
import sys

# The spawning test pins JAX_PLATFORMS=cpu and the 4-device XLA flag in
# this process's ENVIRONMENT.

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_session_and_stream():
    """The (session, stream) pair — ONE definition shared by the
    workers and the in-test golden (the sha256 compare requires exact
    lockstep)."""
    from kme_tpu.engine import seq as SQ
    from kme_tpu.parallel.seqmesh import SeqMeshSession
    from kme_tpu.workload import zipf_symbol_stream

    msgs = zipf_symbol_stream(900, num_symbols=8, num_accounts=24,
                              seed=17, zipf_a=1.0, payout_per_mille=5)
    ses = SeqMeshSession(       # the mesh spans both processes
        SQ.SeqConfig(lanes=8, slots=128, accounts=128, max_fills=16,
                     pos_cap=1 << 10, probe_max=8), shards=8)
    return ses, msgs


def main() -> int:
    coordinator, nprocs, pid, outfile = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4])
    import jax

    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=nprocs, process_id=pid)
    assert jax.device_count() == 4 * nprocs, jax.devices()
    assert jax.process_count() == nprocs

    ses, msgs = build_session_and_stream()
    out = ses.process_wire(msgs)
    blob = "\n".join(l for ls in out for l in ls).encode()
    digest = hashlib.sha256(blob).hexdigest()
    with open(outfile, "w") as f:
        f.write(f"{digest} {len(blob)}\n")
    # keep both processes alive until collectives drain
    jax.effects_barrier()
    return 0


if __name__ == "__main__":
    sys.exit(main())
