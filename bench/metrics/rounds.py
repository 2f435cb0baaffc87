"""Rounds the engine ran (``_run_round`` calls) per materialisation in
the window."""


def read(ctx):
    if not ctx["rounds"] or not ctx["units"]:
        return None
    return ctx["rounds"] / ctx["units"]
