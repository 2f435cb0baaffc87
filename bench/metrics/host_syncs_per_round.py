"""Device->host reads per round: the engine's ``dist.host_syncs`` counter
over its ``dist.rounds``, both from the process's metrics registry
(warm-up and window; they materialise the same facts).  Reported beside
a traced window on the device, like the host round driver's spans."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.ops:
        return None
    from repro.obs import get_registry

    snap = get_registry().snapshot("dist.")
    syncs, rounds = snap.get("dist.host_syncs"), snap.get("dist.rounds")
    if not syncs or not rounds:
        return None
    return syncs / rounds
