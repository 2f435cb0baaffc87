"""Device time of the engine's jitted ``shard_map`` round programs (all
are ``jit_body`` today) per materialisation, in ms."""

from bench import tracereduce

PREFIX = "jit_body"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.modules or not ctx["units"]:
        return None
    per = tracereduce.seconds_by_name(
        tracereduce.in_window(trace, trace.modules),
        lambda name: name.startswith(PREFIX),
    )
    if not per:
        return None
    return 1e3 * sum(per.values()) / len(trace.modules) / ctx["units"]
