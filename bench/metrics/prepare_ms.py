"""Host time of ``DistributedEngine._prepare`` (``dist.prepare``: host
dedup of the explicit facts, routing, ``device_put``) per
materialisation in the traced window, in ms."""

from bench import spanreduce


def read(ctx):
    return spanreduce.per(ctx, "dist.prepare", "dist.materialise")
