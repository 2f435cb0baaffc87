"""Share of its HBM roofline that the Pallas ``sorted_member`` kernel
reaches inside the round programs, in %: the bytes its calls need
(:func:`bench.roofline.sorted_member_bytes`, from the probe and table
operands' shapes, which the op's name in the trace carries) at the
chip's HBM bandwidth, over the kernel's device time.  HBM bounds it: no
int32 vector peak is published for the chip."""

import re

from bench import roofline, tracereduce

KERNEL = "%_sorted_member_jit"
_SHAPE = re.compile(r"s32\[(\d+),(\d+)\]")


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if trace is None or not peaks:
        return None
    seconds = needed = 0.0
    for events in tracereduce.in_window(trace, trace.ops).values():
        for s, e, name in events:
            if not name.startswith(KERNEL):
                continue
            args = name.split("custom-call(", 1)[-1]
            blocks = [int(a) * int(b) for a, b in _SHAPE.findall(args)[:2]]
            if len(blocks) != 2:
                continue
            seconds += (e - s) * 1e-9
            needed += roofline.sorted_member_bytes(*blocks)
    if not seconds:
        return None
    return roofline.share_pct(needed, seconds, peaks["hbm_bytes_per_s"])
