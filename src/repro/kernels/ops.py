"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to ``None`` — "interpret exactly when the jax
backend is CPU", overridable with ``REPRO_PALLAS_INTERPRET`` (see
:mod:`repro.kernels.backend`) — so the same call sites compile the real
Mosaic kernels on TPU/GPU.  When no explicit ``block_*`` sizes are
passed the blocked kernels take them from the autotuner
(:mod:`repro.kernels.tune`): hand-tuned defaults in interpret mode,
cached sweep winners on hardware.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs import get_registry
from .backend import resolve_interpret
from .fused import fused_join_dedup as _fused_join_dedup
from .fused import merge_sorted_unique as _merge_sorted_unique
from .join_bounds import join_bounds as _join_bounds
from .rle_expand import rle_expand as _rle_expand
from .sorted_member import sorted_member as _sorted_member
from .tune import get_blocks

__all__ = [
    "member",
    "anti_join_mask",
    "expand_rle",
    "group_spans",
    "join_dedup",
    "launch_count",
    "merge_unique",
    "meter",
    "meter_reset",
]

# kernel-launch metering lives in the metrics registry under the
# ``kernels.`` scope (``kernels.<op>.calls`` / ``kernels.<op>.elements``,
# plus the cross-op ``kernels.kernel_launches`` total that the bench
# gate watches) — cheap host-side counters so benchmarks and the serving
# driver can report how much work the device path absorbed, resettable
# per scope without clobbering anyone else's metrics.  Counts *eager*
# launches only: inside a jit trace the Python side effect would fire
# once per trace, not per execution, so traced calls are excluded rather
# than silently underreported.
_SCOPE = "kernels."


def _metered(op: str, n, operand=None, launches: int = 1) -> None:
    if isinstance(operand, jax.core.Tracer):
        return
    reg = get_registry()
    reg.counter(f"{_SCOPE}{op}.calls").inc()
    reg.counter(f"{_SCOPE}{op}.elements").inc(int(n))
    reg.counter(f"{_SCOPE}kernel_launches").inc(launches)


def meter() -> dict[str, dict[str, int]]:
    """Snapshot of per-op kernel traffic since the last reset (the
    legacy ``{op: {"calls", "elements"}}`` shape, reassembled from the
    registry's ``kernels.`` scope)."""
    out: dict[str, dict[str, int]] = {}
    for name, val in get_registry().snapshot(_SCOPE).items():
        rest = name[len(_SCOPE):]
        if "." not in rest:
            continue  # scope-level totals (kernel_launches) and gauges
        op, field = rest.rsplit(".", 1)
        if field not in ("calls", "elements"):
            continue
        out.setdefault(op, {"calls": 0, "elements": 0})[field] = int(val)
    # registry reset zeroes in place; drop untouched ops so the dict
    # looks exactly like the legacy meter after meter_reset()
    return {op: m for op, m in out.items() if m["calls"]}


def launch_count() -> int:
    """Total eager kernel launches since the last ``kernels.`` reset."""
    snap = get_registry().snapshot(_SCOPE)
    return int(snap.get(f"{_SCOPE}kernel_launches", 0))


def meter_reset() -> None:
    """Zero the ``kernels.`` registry scope only (other scopes keep
    accumulating — per-scope reset is the whole point)."""
    get_registry().reset(_SCOPE)


def _blocks_for(kernel: str, n: int, interpret, blocks: dict) -> dict:
    """Caller-supplied ``block_*`` win; otherwise ask the autotuner."""
    if blocks:
        return blocks
    return get_blocks(kernel, "int32", n, interpret=interpret)


def member(a, b_sorted, *, a_sorted: bool = False,
           interpret: bool | None = None, **blocks) -> jax.Array:
    """``out[i] = a[i] in b_sorted`` (semi-join filter); ``a_sorted``
    promises ascending probes (see :func:`.sorted_member.sorted_member`)."""
    a = jnp.asarray(a)
    _metered("member", a.size, a)
    interpret = resolve_interpret(interpret)
    blocks = _blocks_for("sorted_member", a.size, interpret, blocks)
    return _sorted_member(
        a, jnp.asarray(b_sorted), a_sorted=a_sorted, interpret=interpret,
        **blocks,
    )


def anti_join_mask(new, old_sorted, *, interpret: bool | None = None, **blocks):
    """Mask of ``new`` elements NOT in ``old_sorted`` (the dedup test of
    Algorithm 6)."""
    return ~member(new, old_sorted, interpret=interpret, **blocks)


def expand_rle(run_values, run_counts, total: int, *,
               interpret: bool | None = None, **blocks):
    """Unfold an RLE leaf meta-constant into ``total`` constants."""
    _metered("expand_rle", int(total), run_values)
    interpret = resolve_interpret(interpret)
    blocks = _blocks_for("rle_expand", int(total), interpret, blocks)
    return _rle_expand(
        jnp.asarray(run_values),
        jnp.asarray(run_counts),
        total=int(total),
        interpret=interpret,
        **blocks,
    )


def group_spans(l_keys, r_sorted, *, interpret: bool | None = None, **blocks):
    """Per-left-key [lo, hi) spans in the sorted right keys — the
    cross-join group locator of Algorithm 5."""
    l_keys = jnp.asarray(l_keys)
    _metered("group_spans", l_keys.size, l_keys)
    interpret = resolve_interpret(interpret)
    blocks = _blocks_for("join_bounds", l_keys.size, interpret, blocks)
    return _join_bounds(
        l_keys, jnp.asarray(r_sorted), interpret=interpret, **blocks
    )


def join_dedup(l_keys, l_payload, r_keys_sorted, r_payload, *,
               capacity: int, interpret: bool | None = None):
    """Fused span-probe → gather → sort → dedup, **one** launch (vs the
    unfused ``group_spans`` + gather + sort + ``member`` chain).  See
    :func:`repro.kernels.fused.fused_join_dedup` for the contract."""
    l_keys = jnp.asarray(l_keys)
    _metered("join_dedup", l_keys.size, l_keys)
    return _fused_join_dedup(
        l_keys,
        jnp.asarray(l_payload),
        jnp.asarray(r_keys_sorted),
        jnp.asarray(r_payload),
        capacity=capacity,
        interpret=resolve_interpret(interpret),
    )


def merge_unique(buf, fresh, *, interpret: bool | None = None):
    """Fused in-place sorted-unique merge, one launch (vs the unfused
    anti-join + concatenate + re-sort chain).  Buffer-donating rounds
    should go through :class:`repro.kernels.buffers.FactBuffers`."""
    fresh = jnp.asarray(fresh)
    _metered("merge_unique", fresh.size, fresh)
    return _merge_sorted_unique(
        jnp.asarray(buf), fresh, interpret=resolve_interpret(interpret)
    )
