"""Device time of the round programs' ``keys`` scope (outermost ops whose
innermost phase scope is ``keys``: packing the target and restrict rows
into keys and sorting them, before the dedup) per materialisation, in
ms.  ``bench.spanreduce`` names the phases ``join``, ``exchange``,
``merge`` and ``dedup``; this reader reads the ops' scopes once more
with ``keys`` among them, so that those phases read as before, and
takes the ops themselves from the trace the harness already read."""

from bench import spanreduce, tracereduce

PHASE = "keys"


def _spans(ctx):
    if "spans.keys" not in ctx:
        spans = None
        found = spanreduce.load(ctx)
        if found is not None:
            from bench import harness

            phases = spanreduce.PHASES
            spanreduce.PHASES = phases + (PHASE,)
            try:
                scopes = spanreduce.op_phases(
                    tracereduce.find_xplane(harness.TRACE_DIR)
                )
            finally:
                spanreduce.PHASES = phases
            spans = spanreduce.build(found.host, {
                d: [(s, e, scopes.get(d, {}).get(name)) for s, e, name in ops]
                for d, ops in ctx["trace"].ops.items()
            })
        ctx["spans.keys"] = spans
    return ctx["spans.keys"]


def read(ctx):
    spans = _spans(ctx)
    # programs built without the scope (before it existed) give nothing
    if spans is None or not any(
        phase == PHASE for ops in spans.ops.values() for *_, phase in ops
    ):
        return None
    return spanreduce.phase_ms({**ctx, "spans": spans}, PHASE)
