"""Share of the traced window in which no op ran on the device, in %."""

from bench import tracereduce


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.ops:
        return None
    return 100.0 * (1.0 - tracereduce.busy_s(trace) / trace.window_s)
