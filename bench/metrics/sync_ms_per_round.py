"""Host time in device->host reads of counts and delta bounds
(``dist.sync``) per round in the traced window, in ms."""

from bench import spanreduce


def read(ctx):
    return spanreduce.per(ctx, "dist.sync", "dist.round")
