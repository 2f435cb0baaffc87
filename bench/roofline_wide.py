"""Bytes a two-word ``sorted_member`` call needs, from its shapes, for
``sorted_member_wide_roofline`` (as :mod:`bench.roofline` gives them for
the one-word kernel; shares from :func:`bench.roofline.share_pct`)."""

from __future__ import annotations

from bench.roofline import INT32


def sorted_member_wide_bytes(n_probes: int, n_table: int) -> int:
    """Membership of ``n_probes`` two-word keys in a sorted table of
    ``n_table`` two-word keys: both words of each probe and of each table
    key read once, one int32 flag written per probe."""
    return INT32 * (3 * n_probes + 2 * n_table)
