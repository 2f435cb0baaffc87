"""Device time of the round programs' ``dedup`` phase (outermost ops
whose innermost phase scope is ``dedup``: sort, first occurrences and
membership of ``dedup_against``) per materialisation, in ms."""

from bench import spanreduce


def read(ctx):
    return spanreduce.phase_ms(ctx, "dedup")
