"""Host time in Python garbage collections (``host.gc``, the span the
``repro.obs`` collector hook opens) per materialisation in the traced
window, in ms."""

from bench import spanreduce


def read(ctx):
    return spanreduce.per(ctx, "host.gc", "dist.materialise")
