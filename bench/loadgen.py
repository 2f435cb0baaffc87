"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and checks them before the harness drives
the work.

Kinds of mix:

* ``materialise_loop`` — the cell's explicit facts, materialised again
  and again in a closed loop (no parameters).
"""

from __future__ import annotations

KINDS = ("materialise_loop",)


def check_mix(traffic: dict) -> None:
    kind = traffic.get("kind")
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r} (use one of {KINDS})")
