"""Host time of the result pull at the end of a materialisation
(``dist.pull``: the buffers read back, concatenated and ``unique_rows``)
per materialisation in the traced window, in ms."""

from bench import spanreduce


def read(ctx):
    return spanreduce.per(ctx, "dist.pull", "dist.materialise")
