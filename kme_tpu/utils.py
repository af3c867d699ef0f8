"""Small shared host-side utilities."""

from __future__ import annotations


def async_prefetch(values) -> None:
    """Start device->host copies for every array in `values` without
    blocking — np.asarray afterwards finds the bytes already in flight."""
    for v in values:
        v.copy_to_host_async()


def pow2_bucket(n: int, lo: int = 64) -> int:
    """Round up to a power-of-two bucket (bounds XLA recompiles for
    shape-dependent host-side slicing/padding)."""
    b = lo
    while b < n:
        b *= 2
    return b
