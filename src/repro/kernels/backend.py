"""Backend detection for the Pallas kernels: resolving ``interpret=None``.

Every kernel wrapper takes ``interpret: bool | None = None``.  ``None``
means "interpret exactly when the jax backend is CPU": on a CPU-only
container the kernel bodies execute in Python for validation, while the
same call sites compile the real Mosaic kernel as soon as a TPU/GPU
backend is present — no code change needed to switch.

The environment variable :data:`ENV_VAR` (``REPRO_PALLAS_INTERPRET``)
overrides the detection: ``0/false/no`` forces compilation (exercising
the Mosaic lowering from a CPU process), ``1/true/yes`` asks for
interpret mode and is honoured only on the CPU backend — on an
accelerator it raises, so a run that looks like a device run always
executes the compiled kernels.  Resolution happens *outside* the jit'd
kernels, so their caches are keyed on the resolved concrete bool.
"""

from __future__ import annotations

import os

__all__ = ["ENV_VAR", "backend_name", "default_interpret", "resolve_interpret"]

#: env override: truthy -> always interpret, falsy -> never interpret
ENV_VAR = "REPRO_PALLAS_INTERPRET"

_TRUTHY = {"1", "true", "yes", "on"}
_FALSY = {"0", "false", "no", "off"}


def backend_name() -> str:
    """The active jax backend ("cpu", "tpu", "gpu").  Imported lazily so
    numpy-only consumers of :mod:`repro.kernels` never pay the jax
    import just to ask."""
    import jax

    return jax.default_backend()


def default_interpret() -> bool:
    """True when kernels should run in interpret mode by default.

    Order: :data:`ENV_VAR` if set (anything unrecognised raises — a typo
    silently flipping the execution path is the worst failure mode, and
    so does asking to interpret on an accelerator), else backend
    detection (CPU -> interpret).
    """
    env = os.environ.get(ENV_VAR)
    if env is not None:
        val = env.strip().lower()
        if val in _TRUTHY:
            backend = backend_name()
            if backend != "cpu":
                raise RuntimeError(
                    f"{ENV_VAR}={env!r} asks for interpret mode on the "
                    f"{backend} backend; kernels interpret only on CPU"
                )
            return True
        if val in _FALSY:
            return False
        raise ValueError(
            f"{ENV_VAR}={env!r}: expected one of "
            f"{sorted(_TRUTHY | _FALSY)}"
        )
    return backend_name() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a wrapper's ``interpret`` argument to a concrete bool."""
    if interpret is None:
        return default_interpret()
    return bool(interpret)
