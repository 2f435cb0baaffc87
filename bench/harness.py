"""One run of one cell: set-up, a measured window, the check, the result.

Everything a cell needs is found by name: the workload in
``BENCHMARK.json`` names a configuration (``bench/configs/<name>.json``,
whose ``generator`` names ``bench/kbgen/<generator>.py``) and a traffic
mix (``bench/traffic/<mix>.json``, read by :mod:`bench.loadgen`); each
per-layer metric is read by ``bench/metrics/<name>.py``, or by the
reader of the part of its name before the first dot.  A configuration's
``program`` names its rule text, ``bench/programs/<program>.txt``.

The window drives ``DistributedEngine.materialise`` in a closed loop
until ``seconds`` have passed, and always ends on a whole
materialisation: its metric is the window's length over the
materialisations it completed.  Nothing is compared
inside the window.  Once it has closed, the device's peak memory is
read, the engine is dropped, and what the window produced is compared
with :mod:`bench.reference`.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

from . import loadgen, reference, tracereduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class BenchError(RuntimeError):
    """The run cannot be measured; it prints no result."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        return None
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """The workload entry, its configuration, its traffic mix and the
    metrics it reports, as ``BENCHMARK.json`` under ``root`` names them."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    e2e = [
        m for m in spec["end_to_end"]
        if name in m.get("workloads", [name])
    ]
    moved = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]
    return {
        "workload": wl,
        "config": load_json(os.path.join(root, entry["file"])),
        "traffic": load_json(
            os.path.join(root, "bench", "traffic", wl["traffic"] + ".json")
        ),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def load_peaks() -> dict:
    return load_json(os.path.join(BENCH_DIR, "peaks.json"))


def device_info(devices, chips: int, peaks: dict, require_tpu: bool = True) -> dict:
    """The device as JAX reports it; raises when it cannot be measured."""
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"no TPU found (JAX platform is {dev.platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX sees {len(devices)}")
    if require_tpu and dev.device_kind not in peaks["devices"]:
        raise BenchError(
            f"device kind {dev.device_kind!r} is not in bench/peaks.json"
        )
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def rules(config: dict) -> list[str]:
    """The lines of the configuration's rule program."""
    path = os.path.join(BENCH_DIR, "programs", config["program"] + ".txt")
    with open(path) as fh:
        return fh.read().splitlines()


def generate(config: dict, seed: int) -> dict[str, np.ndarray]:
    """The explicit facts of a configuration, from ``seed``."""
    path = os.path.join(BENCH_DIR, "kbgen", config["generator"] + ".py")
    mod = _load_module(path, "bench_kbgen_" + config["generator"])
    if mod is None:
        raise BenchError(f"no generator {path}")
    return mod.generate(config["params"], seed % (1 << 63))


class CompileCounter:
    """Counts, through ``jax.monitoring``, the programs lowered, those
    compiled by the backend and those read from the persistent cache."""

    EVENTS = {
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowered",
        "/jax/core/compile/backend_compile_duration": "compiled",
        "/jax/compilation_cache/cache_retrieval_time_sec": "from_cache",
    }

    def __init__(self):
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        self.compile_s = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1
            if key == "compiled":
                self.compile_s += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self)


def instrument(engine) -> list[int]:
    """Wrap the engine's ``_prepare`` and ``_run_round`` in host
    annotations (trace runs only); returns a one-item list that counts
    the rounds.  Called after the warm-up, so that every program is
    traced from the same call stack as in an untraced run, and so has
    the same key in the persistent compilation cache."""
    import jax

    rounds = [0]
    prepare, run_round = engine._prepare, engine._run_round

    def _prepare(*a, **kw):
        with jax.profiler.TraceAnnotation("bench.prepare"):
            return prepare(*a, **kw)

    def _run_round(*a, **kw):
        rounds[0] += 1
        with jax.profiler.TraceAnnotation("bench.round"):
            return run_round(*a, **kw)

    engine._prepare, engine._run_round = _prepare, _run_round
    return rounds


def build_engine(config: dict, chips: int, cls):
    import jax
    from jax.sharding import Mesh
    from repro.core.datalog import parse_program

    program = parse_program("\n".join(rules(config)))
    if len(cls.supported_program(program)) != len(program):
        raise BenchError("the rule program is outside the engine's fragment")
    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("data",))
    return cls(
        program, mesh, capacity=int(config["capacity"]),
        use_pallas_kernels=bool(config["use_pallas_kernels"]),
    )


def _window(seconds: float, step, annotate) -> tuple[float, float, int]:
    """Run ``step()`` until ``seconds`` have passed; ``(start, elapsed,
    units)`` on the host clock."""
    ends = []
    with annotate(tracereduce.WINDOW):
        t0 = time.perf_counter()
        while True:
            step()
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
        elapsed = ends[-1] - t0
    # each unit's time, to tell a slow process from a slow stretch
    log("unit seconds: " + " ".join(
        f"{b - a:.4f}" for a, b in zip([t0, *ends], ends)
    ))
    return t0, elapsed, len(ends)


def _materialise_loop(engine, facts, config, annotate):
    max_rounds = int(config["max_rounds"])
    # the first materialisation builds every round variant the loop uses
    # (the same facts give the same schedule); on the chip the second
    # still ran 2-10% slower than the rest, so it is set-up too
    for _ in range(2):
        engine.materialise(facts, max_rounds=max_rounds)
    outputs = []

    def step():
        with annotate("bench.materialise"):
            outputs.append(engine.materialise(facts, max_rounds=max_rounds))

    return outputs, step


def check_materialise(outputs, facts, lines) -> tuple[dict, int]:
    want, _ = reference.materialise(facts, reference.parse_rules(lines))
    worst = [0, 0]
    failed = 0
    for got in outputs:
        missing, extra = reference.compare(got, want)
        worst = [max(worst[0], missing), max(worst[1], extra)]
        failed += bool(missing or extra)
    return {"missing_facts": worst[0], "extra_facts": worst[1]}, failed


def _peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except (AttributeError, NotImplementedError, RuntimeError):
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _tuner_choices() -> dict:
    from repro.kernels.tune import cache_path

    try:
        return load_json(cache_path()).get("entries", {})
    except (OSError, ValueError):
        return {}


def read_metric(name: str, ctx: dict):
    """A per-layer metric by its reader, or ``None`` (left out)."""
    base = name.split(".", 1)[0]
    for stem in (name, base):
        mod = _load_module(
            os.path.join(BENCH_DIR, "metrics", stem + ".py"),
            "bench_metric_" + stem.replace(".", "_"),
        )
        if mod is not None:
            return mod.read(ctx)
    raise BenchError(f"no reader for per-layer metric {name!r}")


def run_cell(
    cell: dict,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    require_tpu: bool = True,
    engine_cls=None,
) -> dict:
    """One run of ``cell`` (as :func:`load_cell` gives it); returns the
    result line as a dict.  ``engine_cls`` replaces the engine class (the
    tests plant faults through it)."""
    import jax

    from repro.core.distributed import DistributedEngine

    wl, config, traffic = cell["workload"], cell["config"], cell["traffic"]
    chips = int(wl["chips"])
    peaks = load_peaks()
    devices = jax.devices()
    device = device_info(devices, chips, peaks, require_tpu)
    loadgen.check_mix(traffic)
    cls = engine_cls or DistributedEngine
    annotate = jax.profiler.TraceAnnotation

    with CompileCounter() as counter:
        facts = generate(config, seed)
        engine = build_engine(config, chips, cls)
        outputs, step = _materialise_loop(engine, facts, config, annotate)
        log(
            f"set-up: {counter.counts['lowered']} programs lowered, "
            f"{counter.counts['compiled']} compiled "
            f"({counter.compile_s:.3f} s), "
            f"{counter.counts['from_cache']} read from the cache"
        )
        log(f"Pallas tuner choices: {json.dumps(_tuner_choices(), sort_keys=True)}")
        lowered0 = counter.counts["lowered"]
        rounds = [0]
        if trace:
            rounds = instrument(engine)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(TRACE_DIR)
        try:
            t0, elapsed, units = _window(seconds, step, annotate)
        finally:
            if trace:
                jax.profiler.stop_trace()
        lowered_in_window = counter.counts["lowered"] - lowered0
    rounds = rounds[0]
    log(
        f"window: {units} units in {elapsed:.6f} s, {rounds if trace else '-'} "
        f"rounds, {lowered_in_window} programs lowered"
    )
    peak = _peak_bytes(devices[:chips])
    device["memory_peak_bytes"] = peak

    del engine
    gc.collect()

    t_check = time.perf_counter()
    checks, failed = check_materialise(outputs, facts, rules(config))
    log(f"check against the reference: {time.perf_counter() - t_check:.3f} s")
    limits = {name: 0 for name in checks}

    measured = {
        "setup_s": (t0 - t_start, "s"),
        "device_peak_mib": (None if peak is None else peak / 2**20, "MiB"),
        "materialise_s": (elapsed / units, "s"),
    }
    result: dict = {
        "correct": all(checks[k] <= limits[k] for k in checks),
        "attempted": units,
        "failed": int(failed),
        "metrics": {},
        "device": device,
    }
    if not trace:
        for m in cell["end_to_end"]:
            value, unit = measured.get(m["name"], (None, m["unit"]))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": unit}
    else:
        reduced = None
        path = tracereduce.find_xplane(TRACE_DIR)
        if path is not None:
            reduced = tracereduce.from_xplane(path)
        ctx = {
            "trace": reduced,
            "units": units,
            "rounds": rounds,
            "peaks": peaks["devices"].get(device["kind"]),
        }
        for m in cell["per_layer"]:
            value = read_metric(m["name"], ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None:
            busy = tracereduce.busy_s(reduced)
            device["busy_s"] = busy
            device["window_s"] = reduced.window_s
            ops = tracereduce.outermost(tracereduce.in_window(reduced, reduced.ops))
            result["breakdown"] = {
                "device_ops": tracereduce.top(tracereduce.seconds_by_name({
                    d: [(s, e, tracereduce.short_name(n)) for s, e, n in v]
                    for d, v in ops.items()
                })),
                "idle_gaps": tracereduce.top(tracereduce.idle_by_annotation(reduced)),
            }
    result["checks"] = {
        name: {"value": checks[name], "limit": limits[name]} for name in checks
    }
    for name in checks:
        log(f"check {name} = {checks[name]} (limit {limits[name]})")
    return result


def use_checkout_cache(root: str = ROOT) -> str:
    """Point JAX's persistent compilation cache and the Pallas tuner's
    table at ``<checkout>/.jax_cache``, the program's own fixed cache
    directory (``repro.compile_cache``); call before JAX starts."""
    found = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache = os.path.join(root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(cache, "pallas_tune.json")
    # every program, however quick to compile, is read back next run
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no size limit, so no LRU eviction and no "-atime" side files: on a
    # TPU v5e host with a size limit set, those writes failed, and with
    # them every cache entry
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    log(f"JAX_COMPILATION_CACHE_DIR was {found!r}; cache directory {cache}")
    return cache
