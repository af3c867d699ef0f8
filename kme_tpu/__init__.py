"""kme_tpu — TPU-native matching-engine framework.

A ground-up JAX/XLA/Pallas/pjit re-design of the capabilities of the
reference VD44/Kafka-Matching-Engine (a Kafka Streams limit-order-book
processor, /root/reference/src/main/java/KProcessor.java): prediction-market
style binary-outcome contracts, integer prices 0..125, margin `price` per
unit for buys and `100 - price` per unit for sells
(KProcessor.java:167-182), account ledgers, pre-trade risk checks,
price-time-priority matching, cancels, and symbol settlement.

Instead of one message at a time against five RocksDB stores, this framework
keeps the entire exchange state resident in dense device arrays (HBM, the
working set in VMEM) and processes a micro-batch strictly in arrival order
inside ONE Pallas kernel call (engine/seq.py); the sharded form
(parallel/seqmesh.py) splits the symbol axis over a TPU mesh with
`shard_map`, merging cross-shard account-balance deltas with exact integer
`psum` collectives over ICI.

Package layout:
  oracle/    quirk-faithful pure-Python replica of the reference semantics
             (the golden parity judge; compat='java' and compat='fixed')
  engine/    the device engines: seq.py (the served one: a sequential
             Pallas mega-kernel, on-device metrics, packed fill log) and
             parity.py (serial quirk-exact replica as one lax.scan, a
             reference)
  ops/       exact bit/codec device utilities and associative tables
             (parity.py's)
  parallel/  seqmesh.py: the seq engine over a mesh, psum-merged
  runtime/   host runtime: the id router and the batching session with
             compact device I/O (seqsession.py), checkpoint/resume
             (checkpoint.py, javasnap.py)
  native/    the host path's C++ (router, plan + pack, wire parse and
             reconstruction) and the native quirk-exact engine
  bridge/    transport edge speaking the reference's Kafka wire contract:
             broker core with durable logs, TCP process boundary, and the
             MatchIn -> engine -> MatchOut service + CLIs
  wire/workload/opcodes/cli  byte-exact serde, seeded harness
             workloads, protocol constants, entry points

Compatibility envelope and mode matrix: COMPAT.md at the repo root.
The top-level package is import-light: the pure-Python layers (wire,
oracle, workload) work without JAX. Device modules (engine/, ops/,
parallel/) import `kme_tpu._jaxsetup` which enables x64 once.
"""

__version__ = "0.1.0"

import os as _os

if _os.environ.get("KME_LOCKCHECK") == "1":
    # opt-in lock-order recorder: must patch threading.Lock/RLock
    # before any kme_tpu module allocates a lock, hence here at the
    # package root. See kme_tpu/analysis/lockcheck.py; tier-1 runs
    # with this set assert no inversions at session teardown.
    from kme_tpu.analysis import lockcheck as _lockcheck

    _lockcheck.install()

from kme_tpu import opcodes  # noqa: F401
