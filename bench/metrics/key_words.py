"""Int32 words of a binary fact's key in the engine's programs: the
engine's ``dist.key_words`` gauge, set by every ``materialise``, from the
process's metrics registry (1 while every id is below ``2**15``, else 2).
Reported beside a traced window on the device, like the engine's other
counters; nothing where the engine sets no such gauge."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.ops:
        return None
    from repro.obs import get_registry

    return get_registry().snapshot("dist.").get("dist.key_words") or None
