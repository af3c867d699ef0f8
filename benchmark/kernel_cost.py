"""Bytes one call of the seq scan program (`jit_call_scan`, one kernel
invocation per served batch) has to move between HBM and the core: the
message planes in and the output plane out. The book planes stay where
they are (with `hbm_books` the kernel moves one lane's rows per lane
switch, which an ideal kernel need not), so this is the least the call
must move and the roofline share built on it is a share of the HBM
bound.

The shapes are `SeqConfig`'s (kme_tpu/engine/seq.py): the program is
imported only by the test that checks these constants against it, so
that the benchmark's parent process stays free of jax."""

from __future__ import annotations

LANE = 128              # LN
KERNEL_BATCH = 4096     # SeqConfig.batch: message slots per kernel call
FILL_CAP = 1 << 15      # SeqConfig.fill_cap: fill entries per call
MSG_PLANES = {"fixed": 7, "java": 12}   # build_seq_step MSG_FIELDS


def out_rows(batch: int = KERNEL_BATCH, fill_cap: int = FILL_CAP) -> int:
    """Rows of the output plane: one scalar row, five regions per
    message row, five rows per 128 fills (seq.out_rows)."""
    return 1 + 5 * (batch // LANE) + 5 * (fill_cap // LANE)


def serve_option(config: dict, flag: str) -> str:
    args = config["serve"]
    return args[args.index(flag) + 1]


def seq_call_bytes(config: dict) -> int:
    """Least bytes of one kernel call for a configuration file."""
    planes = MSG_PLANES[serve_option(config, "--compat")]
    return 4 * (planes * KERNEL_BATCH + out_rows() * LANE)
