"""Shared fixtures: the observability leak check.

Every tier-1 module runs under ``leak_check``: the obs singletons
(metrics registry, span tracer, memory accountant) are process-wide,
so a test that swaps one out, leaves the tracer enabled, forgets a
sampler hook, or keeps ``FactBuffers`` capacity alive would silently
tax every module that runs after it.  The fixture pins the baseline at
module entry and asserts it is restored at module exit (after a
``gc.collect()`` so weakly-registered reporters whose owners died are
actually gone), then clears the ``mem.`` gauge scope so one module's
watermarks never masquerade as the next module's.
"""

from __future__ import annotations

import gc

import pytest


@pytest.fixture(autouse=True, scope="module")
def leak_check():
    from repro.obs import get_registry, get_tracer
    from repro.obs.memory import get_accountant

    gc.collect()
    reg = get_registry()
    tr = get_tracer()
    acc = get_accountant()
    from repro.obs.provenance import get_journal

    journal = get_journal()
    prov_was = journal.enabled
    was_enabled = tr.enabled
    n_hooks = len(tr.hooks)
    cap0 = sum(b.capacity_bytes() for b in acc.live().get("buffers", []))

    yield

    gc.collect()
    from repro.obs import get_registry as gr
    from repro.obs import get_tracer as gt
    from repro.obs.memory import get_accountant as ga

    assert gr() is reg, "metrics registry singleton swapped mid-module"
    assert gt() is tr, "span tracer singleton swapped mid-module"
    assert ga() is acc, "memory accountant singleton swapped mid-module"
    assert tr.enabled == was_enabled, "tracer enable state leaked"
    assert journal.enabled == prov_was, (
        "provenance journal enable state leaked"
    )
    assert len(tr.hooks) == n_hooks, "tracer hooks leaked (sampler not detached?)"
    cap1 = sum(b.capacity_bytes() for b in acc.live().get("buffers", []))
    assert cap1 <= cap0, (
        f"FactBuffers capacity leaked across the module: "
        f"{cap0}B at entry -> {cap1}B at exit"
    )
    reg.reset("mem.")


@pytest.fixture
def profile_host_events(tmp_path):
    """``run(fn)`` calls ``fn()`` inside a ``jax.profiler`` session and
    returns the host events of its ``.xplane.pb`` as ``(name, start_ns,
    end_ns, stats)``, in start order."""
    import glob

    import jax

    def run(fn):
        jax.profiler.start_trace(str(tmp_path))
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
        out = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    out.extend(
                        (e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns), dict(e.stats))
                        for e in line.events
                    )
        return sorted(out, key=lambda ev: (ev[1], -ev[2]))

    return run
