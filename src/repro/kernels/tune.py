"""Block-size autotuner for the Pallas kernels.

The blocked kernels (``sorted_member``, ``join_bounds``, ``rle_expand``)
take ``block_*`` sizes that trade VMEM residency against grid overhead;
the right choice depends on the backend and the operand size.  This
module picks them per ``(kernel, dtype, size-bucket)`` from a one-shot
timing sweep:

* **buckets** — operand sizes are bucketed to the next power of two
  (floor 256), so one sweep covers every size in the bucket and the
  disk cache stays small,
* **sweep** — each candidate block assignment is timed best-of-3 on
  synthetic sorted operands of the bucket size (``block_until_ready``
  so device time is measured, not dispatch), and the fastest wins.  The
  sweep runs eagerly, on a thread of its own, even when the lookup
  happens while a caller is being traced.  A sweep in which no
  candidate runs raises: there is no
  silent fallback to untested defaults on hardware,
* **cache** — winners persist to a JSON file (:func:`cache_path`,
  ``pallas_tune.json`` in the checkout's fixed cache directory;
  override with ``REPRO_TUNE_CACHE``) keyed by
  ``kernel|dtype|bucket|backend``.

Invalidation rules: the file carries ``{"version", "jax"}`` — a version
bump or a jax upgrade discards the whole cache (kernel lowerings
change); the backend lives in every entry key, so a cache written on
CPU never serves a TPU process.  Corrupt or unreadable files are
treated as empty, never an error.

In interpret mode the sweep is skipped entirely and the hand-tuned
defaults are returned: timing the Python emulation would tune for the
emulator, not the hardware.  Traffic is surfaced through the
``kernels.`` metrics scope — ``kernels.tune.cache_hits`` /
``kernels.tune.sweeps`` / ``kernels.tune.defaults``.
"""

from __future__ import annotations

import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

from ..compile_cache import CACHE_DIR
from ..obs import get_registry, span
from .backend import backend_name, resolve_interpret

__all__ = [
    "CACHE_VERSION",
    "DEFAULTS",
    "cache_path",
    "clear_cache",
    "get_blocks",
    "size_bucket",
]

CACHE_VERSION = 2

#: hand-tuned defaults, whole (8, 128) int32 tiles — returned without a
#: sweep in interpret mode
DEFAULTS: dict[str, dict[str, int]] = {
    "sorted_member": {"block_a": 1024, "block_b": 1024},
    "join_bounds": {"block_l": 1024, "block_r": 1024},
    "rle_expand": {"block_out": 1024},
}

#: candidate assignments swept per kernel (defaults always included)
CANDIDATES: dict[str, list[dict[str, int]]] = {
    "sorted_member": [
        {"block_a": a, "block_b": b} for a in (1024, 2048) for b in (1024, 2048)
    ],
    "join_bounds": [
        {"block_l": a, "block_r": b} for a in (1024, 2048) for b in (1024, 2048)
    ],
    "rle_expand": [{"block_out": b} for b in (1024, 2048, 4096)],
}

_cache: dict[str, dict[str, int]] | None = None  # in-process mirror


def cache_path() -> str:
    return os.environ.get("REPRO_TUNE_CACHE") or os.path.join(
        CACHE_DIR, "pallas_tune.json"
    )


def size_bucket(n: int) -> int:
    """Power-of-two bucket (floor 256) a size-``n`` operand tunes in."""
    n = max(int(n), 1)
    return max(256, 1 << (n - 1).bit_length())


def _load_cache() -> dict[str, dict[str, int]]:
    global _cache
    if _cache is not None:
        return _cache
    _cache = {}
    try:
        with open(cache_path()) as fh:
            raw = json.load(fh)
        import jax

        if (
            isinstance(raw, dict)
            and raw.get("version") == CACHE_VERSION
            and raw.get("jax") == jax.__version__
        ):
            _cache = {
                k: v for k, v in raw.get("entries", {}).items()
                if isinstance(v, dict)
            }
    except (OSError, ValueError):
        pass  # missing/corrupt cache is just a cold cache
    return _cache


def _save_cache() -> None:
    import jax

    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            json.dump(
                {
                    "version": CACHE_VERSION,
                    "jax": jax.__version__,
                    "entries": _cache or {},
                },
                fh,
                indent=2,
                sort_keys=True,
            )
    except OSError:
        pass  # read-only FS: tuning still works, it just re-sweeps


def clear_cache() -> None:
    """Drop the in-process mirror and the disk file (tests)."""
    global _cache
    _cache = None
    try:
        os.unlink(cache_path())
    except OSError:
        pass


# ------------------------------------------------------------------ #
# sweep runners: synthetic operands of the bucket size per kernel
# ------------------------------------------------------------------ #
def _runner(kernel: str, bucket: int, blocks: dict[str, int], interpret: bool):
    import jax.numpy as jnp

    if kernel == "sorted_member":
        from .sorted_member import sorted_member

        a = jnp.arange(bucket, dtype=jnp.int32) * 3
        b = jnp.arange(bucket, dtype=jnp.int32) * 2
        out = sorted_member(a, b, a_sorted=True, interpret=interpret, **blocks)
    elif kernel == "join_bounds":
        from .join_bounds import join_bounds

        a = jnp.arange(bucket, dtype=jnp.int32) * 3
        b = jnp.arange(bucket, dtype=jnp.int32) * 2
        out = join_bounds(a, b, interpret=interpret, **blocks)[0]
    elif kernel == "rle_expand":
        from .rle_expand import rle_expand

        runs = max(bucket // 8, 1)
        vals = jnp.arange(runs, dtype=jnp.int32)
        counts = jnp.full((runs,), 8, dtype=jnp.int32)
        out = rle_expand(
            vals, counts, total=runs * 8, interpret=interpret, **blocks
        )
    else:
        raise KeyError(kernel)
    out.block_until_ready()


def _sweep(kernel: str, bucket: int, interpret: bool) -> dict[str, int]:
    # a lookup can come while a caller is being traced (the sharded round
    # calls the kernel inside its shard_map); JAX keeps trace state per
    # thread, so the timing runs eagerly on a thread of its own
    with ThreadPoolExecutor(1) as ex:
        return ex.submit(_sweep_eager, kernel, bucket, interpret).result()


def _sweep_eager(kernel: str, bucket: int, interpret: bool) -> dict[str, int]:
    with span(
        "kernels.tune.sweep",
        kernel=kernel,
        bucket=bucket,
        candidates=len(CANDIDATES[kernel]),
    ) as sp:
        best_blocks, best_t = None, float("inf")
        errors = []
        for blocks in CANDIDATES[kernel]:
            try:
                _runner(kernel, bucket, blocks, interpret)  # compile + warm
                t = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    _runner(kernel, bucket, blocks, interpret)
                    t = min(t, time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001 — an invalid tiling just loses
                first_line = (str(e).splitlines() or [""])[0]
                errors.append(f"{blocks}: {type(e).__name__}: {first_line}")
                continue
            if t < best_t:
                best_blocks, best_t = blocks, t
        if best_blocks is None:
            raise RuntimeError(
                f"no {kernel} tiling runs on the {backend_name()} backend "
                f"(bucket {bucket}): " + "; ".join(errors)
            )
        sp.set(best=str(dict(best_blocks)), best_s=best_t)
    return dict(best_blocks)


def get_blocks(
    kernel: str,
    dtype: str = "int32",
    n: int = 0,
    *,
    interpret: bool | None = None,
) -> dict[str, int]:
    """Best-known ``block_*`` kwargs for ``kernel`` on a size-``n``
    operand — cached sweep result, or the hand-tuned defaults when
    interpreting (sweeping the emulator tunes the emulator)."""
    reg = get_registry()
    interp = resolve_interpret(interpret)
    defaults = DEFAULTS.get(kernel)
    if defaults is None:
        raise KeyError(f"no tuning table for kernel {kernel!r}")
    if interp:
        reg.counter("kernels.tune.defaults").inc()
        return dict(defaults)
    bucket = size_bucket(n)
    key = f"{kernel}|{dtype}|{bucket}|{backend_name()}"
    cache = _load_cache()
    hit = cache.get(key)
    if hit is not None:
        reg.counter("kernels.tune.cache_hits").inc()
        return dict(hit)
    blocks = _sweep(kernel, bucket, interp)
    cache[key] = blocks
    _save_cache()
    reg.counter("kernels.tune.sweeps").inc()
    return dict(blocks)
