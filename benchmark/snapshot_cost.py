"""Bytes one call of the live-entry program (`jit_live_positions`,
kme_tpu/engine/seq.py:build_seq_live_positions: one call or more per
fixed-mode snapshot) has to move between HBM and the core: the position
store read once, and the count and one chunk of indices and words
written. What the program moves besides (a row of 2 KB gathered for
every entry returned, the rows' counts and ranks) an ideal one need
not, so this is the least the call must move and the roofline share
built on it is a share of the HBM bound.

The shapes are `SeqConfig`'s: the program is imported only by the test
that checks these constants against it (benchmark/test_brokerage.py),
so that the benchmark's parent process stays free of jax."""

from __future__ import annotations

from benchmark.kernel_cost import LANE, serve_option

POS_TILE_ACCOUNTS = 256     # accounts a 4 KB tile of the store holds
POS_TILE_BYTES = 4096
ENTRY_BYTES = 4 + 4 * 4     # an index and four words


def store_shape(config: dict) -> tuple:
    """(lanes, accounts) as bridge/service.py:_seq_cfg gives them."""
    accounts = int(serve_option(config, "--accounts"))
    return (int(serve_option(config, "--symbols")),
            -(-accounts // LANE) * LANE)


def pos_plane_bytes(config: dict) -> int:
    """SeqConfig.pos_rows x 128 words."""
    lanes, accounts = store_shape(config)
    return lanes * -(-accounts // POS_TILE_ACCOUNTS) * POS_TILE_BYTES


def live_positions_chunk(config: dict) -> int:
    """seq.live_positions_chunk: entries a call returns."""
    lanes, accounts = store_shape(config)
    whole = -(-lanes * accounts // LANE) * LANE
    return min(whole, 262144, max(8192, -(-whole // 64 // LANE) * LANE))


def pos_gather_bytes(config: dict) -> int:
    """Least bytes of one call for a configuration file."""
    return (pos_plane_bytes(config) + 4
            + live_positions_chunk(config) * ENTRY_BYTES)
