"""Host planning time per round in the traced window, in ms: the
``dist.schedule`` spans (early-exit check, ``_schedule``, ``_resolve``,
stratification) less the ``dist.sync`` reads inside them."""

from bench import spanreduce


def read(ctx):
    return spanreduce.per(
        ctx, "dist.schedule", "dist.round", minus=("dist.sync",)
    )
