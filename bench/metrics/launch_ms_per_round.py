"""Host time enqueueing round programs (``dist.launch``: variant lookup
and the call) per round in the traced window, in ms."""

from bench import spanreduce


def read(ctx):
    return spanreduce.per(ctx, "dist.launch", "dist.round")
