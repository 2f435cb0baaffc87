"""Reduce the engine's own spans and the round program's phase scopes to
the numbers the host round driver's and the round program's per-layer
metrics read.

Both come from the ``.xplane.pb`` the harness wrote for the window
(:func:`bench.tracereduce.find_xplane`), read once a run:

* engine spans: every host event named ``dist.*`` or ``host.*`` — the
  TraceMe events that ``repro.obs.span`` emits while a profiler session
  is active, and the ``host.gc`` spans of its garbage-collection hook —
  as ``(start_ns, end_ns, name, line)``;
* device ops: each op of a device's ``XLA Ops`` line with its phase, the
  innermost of the round program's ``jax.named_scope`` phases (``join``,
  ``exchange``, ``merge``, ``dedup``) in the name scope the op carries,
  ``None`` for an op outside them.  The scope is the ``tf_op`` stat of
  the op's event *metadata*, which ``jax.profiler.ProfileData`` does not
  expose: :func:`op_phases` reads it from the file's protobuf, keyed by
  the op's name (its HLO text).  A ``while`` carries no scope on a v5e;
  an outermost op without one takes the phase of the ops that ran
  inside it when they all have the same one.

Times are clipped to the harness's window (``ctx["trace"].window``);
a span counts in a denominator only when it lies wholly inside it.
Without a device trace (the CPU) nothing is read.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from bench import tracereduce

PREFIXES = ("dist.", "host.")
#: the spans in which the host does one thing: no engine span nests in
#: them except ``dist.sync`` in ``dist.schedule``
LEAVES = (
    "dist.prepare", "dist.schedule", "dist.sync", "dist.launch",
    "dist.wait", "dist.pull", "host.gc",
)
PHASES = ("join", "exchange", "merge", "dedup")
#: the stat of a device op's event metadata that carries its name scope
SCOPE_STAT = "tf_op"


@dataclass
class Spans:
    #: [(start, end, name, line)] engine spans, sorted
    host: list = field(default_factory=list)
    #: device id -> sorted [(start, end, phase)] ops
    ops: dict = field(default_factory=dict)


def phase_of(scope: str | None) -> str | None:
    """The innermost round-program phase named in an op's scope path."""
    if not scope:
        return None
    for part in reversed(scope.split("/")):
        if part in PHASES:
            return part
    return None


def _xspace_schema():
    """A message class for the part of ``tsl``'s ``XSpace`` protobuf that
    the scopes need: each plane's name, event metadata (name and stats)
    and stat metadata (names); the rest of the file is skipped.  Maps are
    declared as their wire form, repeated key/value entries."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FieldDescriptorProto
    file = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3"
    )

    def message(name, *fields):
        m = file.message_type.add(name=name)
        for fname, number, kind, of in fields:
            field = m.field.add(
                name=fname, number=number,
                label=f.LABEL_REPEATED if of else f.LABEL_OPTIONAL,
                type=f.TYPE_MESSAGE if of else kind,
            )
            if of:
                field.type_name = ".bench_xspace." + of

    message("Stat", ("metadata_id", 1, f.TYPE_INT64, None),
            ("str_value", 5, f.TYPE_STRING, None),
            ("ref_value", 7, f.TYPE_UINT64, None))
    message("EventMetadata", ("name", 2, f.TYPE_STRING, None),
            ("stats", 5, None, "Stat"))
    message("StatMetadata", ("name", 2, f.TYPE_STRING, None))
    for kind in ("EventMetadata", "StatMetadata"):
        message(kind + "Entry", ("key", 1, f.TYPE_INT64, None))
        file.message_type[-1].field.add(
            name="value", number=2, label=f.LABEL_OPTIONAL,
            type=f.TYPE_MESSAGE, type_name=".bench_xspace." + kind,
        )
    message("Plane", ("name", 2, f.TYPE_STRING, None),
            ("event_metadata", 4, None, "EventMetadataEntry"),
            ("stat_metadata", 5, None, "StatMetadataEntry"))
    message("Space", ("planes", 1, None, "Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(file)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace.Space")
    )


def op_phases(path: str) -> dict[int, dict[str, str | None]]:
    """Device id -> {op name: the phase of its ``tf_op`` scope}; ``None``
    for a name that carries no phase, or, in different programs,
    different ones."""
    space = _xspace_schema()()
    with open(path, "rb") as fh:
        space.ParseFromString(fh.read())
    out = {}
    for plane in space.planes:
        dev = tracereduce._device_id(plane.name)
        if dev is None:
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        phases: dict[str, set] = {}
        for entry in plane.event_metadata:
            md = entry.value
            scope = None
            for st in md.stats:
                if names.get(st.metadata_id) == SCOPE_STAT:
                    scope = st.str_value or names.get(st.ref_value)
            phases.setdefault(md.name, set()).add(phase_of(scope))
        out[dev] = {
            n: next(iter(v)) if len(v) == 1 else None for n, v in phases.items()
        }
    return out


def from_xplane(path: str) -> Spans:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    phases = op_phases(path)
    host, ops = [], {}
    for plane in data.planes:
        dev = tracereduce._device_id(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == "XLA Ops":
                phase = phases.get(dev, {})
                ops.setdefault(dev, []).extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns),
                     phase.get(e.name))
                    for e in line.events
                )
            elif dev is None and plane.name.startswith("/host:"):
                key = (plane.name, line.name)
                host.extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name, key)
                    for e in line.events
                    if e.name.startswith(PREFIXES)
                )
    return build(host, ops)


def build(host: list, ops: dict) -> Spans:
    return Spans(
        host=sorted(host), ops={d: sorted(v) for d, v in ops.items()}
    )


def load(ctx) -> Spans | None:
    """The run's spans, read from its trace by the first reader that asks
    and kept in ``ctx``; ``None`` without a device trace or engine spans."""
    if "spans" not in ctx:
        spans = None
        trace = ctx["trace"]
        if trace is not None and trace.ops:
            from bench import harness

            path = tracereduce.find_xplane(harness.TRACE_DIR)
            if path is not None:
                spans = from_xplane(path)
        ctx["spans"] = spans
    spans = ctx["spans"]
    return spans if spans is not None and spans.host else None


def count(spans: Spans, window, name: str) -> int:
    lo, hi = window
    return sum(1 for s, e, n, _ in spans.host if n == name and lo <= s and e <= hi)


def _covered(spans: Spans, window, names) -> list[tuple[int, int]]:
    """Merged intervals in which some span of ``names`` is open."""
    lo, hi = window
    return tracereduce.union(
        [(s, e) for s, e, n, _ in spans.host if n in names], lo, hi
    )


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _overlap(a, b) -> int:
    """Length of the intersection of two merged interval lists."""
    out, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def seconds(spans: Spans, window, name: str, minus=()) -> float:
    """Seconds in which a ``name`` span is open and no ``minus`` span."""
    mine = _covered(spans, window, (name,))
    taken = _overlap(mine, _covered(spans, window, minus)) if minus else 0
    return (_length(mine) - taken) * 1e-9


def per(ctx, name: str, unit: str, minus=()) -> float | None:
    """ms of ``name`` spans (less their ``minus`` children) per
    materialisation (``unit`` ``dist.materialise``) or per round
    (``dist.round``) of the window."""
    spans = load(ctx)
    if spans is None:
        return None
    window = ctx["trace"].window
    n = count(spans, window, unit)
    if not n:
        return None
    return 1e3 * seconds(spans, window, name, minus) / n


def outer_phases(ops: list) -> list:
    """The outermost of ``(start, end, phase)`` ops; one without a phase
    takes the phase of the ops nested in it when they agree."""
    ops = sorted(ops, key=lambda op: (op[0], -op[1]))
    out, i = [], 0
    while i < len(ops):
        s, e, phase = ops[i]
        inner, i = set(), i + 1
        while i < len(ops) and ops[i][0] < e:
            if ops[i][2] is not None:
                inner.add(ops[i][2])
            i += 1
        if phase is None and len(inner) == 1:
            phase = inner.pop()
        out.append((s, e, phase))
    return out


def phase_ms(ctx, phase: str) -> float | None:
    """Device ms per materialisation of the outermost ops whose phase is
    ``phase``, averaged over the devices; ``None`` when no op carries a
    phase (a trace without scopes)."""
    spans = load(ctx)
    if spans is None or not spans.ops:
        return None
    window = ctx["trace"].window
    n = count(spans, window, "dist.materialise")
    outer = {
        d: outer_phases(v)
        for d, v in tracereduce.in_window(ctx["trace"], spans.ops).items()
    }
    if not n or not any(p for v in outer.values() for *_, p in v):
        return None
    total = sum(e - s for v in outer.values() for s, e, p in v if p == phase)
    return 1e3 * total * 1e-9 / len(outer) / n


def idle_unattributed_pct(ctx) -> float | None:
    """Share of the window's device-idle time in which no leaf engine span
    is open, in %, averaged over the devices."""
    spans = load(ctx)
    trace = ctx["trace"]
    if spans is None:
        return None
    lo, hi = trace.window
    leaves = _covered(spans, trace.window, LEAVES)
    shares = []
    for v in trace.ops.values():
        idle = tracereduce.gaps(tracereduce.union(v, lo, hi), lo, hi)
        total = _length(idle)
        if total:
            shares.append(100.0 * (total - _overlap(idle, leaves)) / total)
    return sum(shares) / len(shares) if shares else None
