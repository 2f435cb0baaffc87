"""Device time of the round programs' ``join`` phase (outermost ops
whose innermost phase scope is ``join``) per materialisation, in ms."""

from bench import spanreduce


def read(ctx):
    return spanreduce.phase_ms(ctx, "join")
