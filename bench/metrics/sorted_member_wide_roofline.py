"""Share of its HBM roofline that the two-word Pallas ``sorted_member``
kernel (``_sorted_member_wide_jit``) reaches inside the round programs,
in %: the bytes its calls need
(:func:`bench.roofline_wide.sorted_member_wide_bytes`, from the probe and
table operands' shapes, which the op's name in the trace carries: the
high and low words of the probes, then of the table) at the chip's HBM
bandwidth, over the kernel's device time."""

import re

from bench import roofline, roofline_wide, tracereduce

KERNEL = "%_sorted_member_wide_jit"
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
            words = [int(a) * int(b) for a, b in _SHAPE.findall(args)[:4]]
            if len(words) != 4:
                continue
            seconds += (e - s) * 1e-9
            needed += roofline_wide.sorted_member_wide_bytes(words[0], words[2])
    if not seconds:
        return None
    return roofline.share_pct(needed, seconds, peaks["hbm_bytes_per_s"])
