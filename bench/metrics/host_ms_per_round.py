"""Device-idle time in the traced window per round the engine ran, in ms:
the host's share of a round (scheduling, scalar syncs, staging, result
pull) that the device waits for."""

from bench import tracereduce


def read(ctx):
    trace, rounds = ctx["trace"], ctx["rounds"]
    if trace is None or not trace.ops or not rounds:
        return None
    idle = trace.window_s - tracereduce.busy_s(trace)
    return 1e3 * idle / rounds
