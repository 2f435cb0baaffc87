"""Share of the traced window's device-idle time in which no leaf engine
span (``dist.prepare/schedule/sync/launch/wait/pull``, ``host.gc``) is
open, in %: the idle time the engine's spans do not explain."""

from bench import spanreduce


def read(ctx):
    return spanreduce.idle_unattributed_pct(ctx)
