"""Bytes a kernel call needs, from its shapes, for roofline shares.

A share is the least time the chip could take, the bytes below over the
HBM bandwidth in ``bench/peaks.json``, divided by the kernel's device
time.  The kernels here compare int32 keys on the vector unit, and no
int32 vector peak is published for a v5e, so their shares are bound by
HBM bytes alone.
"""

from __future__ import annotations

INT32 = 4


def sorted_member_bytes(n_probes: int, n_table: int) -> int:
    """Membership of ``n_probes`` int32 keys in a sorted table of
    ``n_table`` int32 keys: each probe and each table key read once, one
    int32 flag written per probe."""
    return INT32 * (2 * n_probes + n_table)


def share_pct(bytes_needed: float, seconds: float, hbm_bytes_per_s: float) -> float:
    return 100.0 * (bytes_needed / hbm_bytes_per_s) / seconds
