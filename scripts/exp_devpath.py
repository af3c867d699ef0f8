"""Measure the seq kernel's transfer-free device path on the real chip.

Method (benchmarks._device_path): AOT-compile the K-chunk scan, then
time [enqueue + device + one small fetch barrier] for the FULL stream
and for a single-chunk scan; the difference cancels the constant
per-dispatch round trip. Each timing is repeated and the minimum
taken. The barrier is an np.asarray of the (1,128) err plane.

Usage: python scripts/exp_devpath.py [slots] [events] [reps]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import kme_tpu._jaxsetup  # noqa: F401
import jax
import numpy as np

from kme_tpu.engine import seq as SQ
from kme_tpu.runtime.seqsession import SeqSession
from kme_tpu.wire import WireBatch, dumps_order
from kme_tpu.workload import zipf_symbol_stream


def main():
    slots = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    events = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000
    reps = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    print(f"backend={jax.devices()[0].platform} slots={slots}", file=sys.stderr)

    msgs = zipf_symbol_stream(events, num_symbols=1024, num_accounts=2048,
                              seed=0, zipf_a=1.2)
    batch = WireBatch.from_msgs(msgs)
    cfg = SQ.SeqConfig(lanes=1024, slots=slots, accounts=2048,
                       max_fills=16, batch=4096, hbm_books=slots > 512)
    ses = SeqSession(cfg)
    t0 = time.perf_counter()
    cols, hr, stacked, cnts, K = ses._plan(batch)
    print(f"plan {time.perf_counter()-t0:.3f}s K={K} n={len(cols['act'])}",
          file=sys.stderr)

    state0 = ses.state
    small = {f: v[:1] for f, v in stacked.items()}
    full_d = jax.device_put(stacked)
    small_d = jax.device_put(small)

    scanK = SQ.build_seq_scan(cfg, K)
    scan1 = SQ.build_seq_scan(cfg, 1)
    t0 = time.perf_counter()
    cK = scanK.lower(state0, full_d).compile()
    c1 = scan1.lower(state0, small_d).compile()
    print(f"AOT compile {time.perf_counter()-t0:.1f}s", file=sys.stderr)

    def timed(compiled, st, inp):
        t0 = time.perf_counter()
        st2, _out = compiled(st, inp)
        np.asarray(st2["err"])  # completion barrier (512B fetch)
        return time.perf_counter() - t0

    # warm both (first dispatch may carry lazy init)
    timed(c1, state0, small_d)
    timed(cK, state0, full_d)
    t_small = [timed(c1, state0, small_d) for _ in range(reps)]
    t_full = [timed(cK, state0, full_d) for _ in range(reps)]
    n = len(cols["act"])
    dev = min(t_full) - min(t_small)
    print(f"t_full={[round(x,4) for x in t_full]}", file=sys.stderr)
    print(f"t_small={[round(x,4) for x in t_small]}", file=sys.stderr)
    print(f"device ~= {dev*1e3:.1f} ms for {n} msgs "
          f"({n/max(dev,1e-9)/1e6:.2f} M msg/s)", file=sys.stderr)


if __name__ == "__main__":
    main()
