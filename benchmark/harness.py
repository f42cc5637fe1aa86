"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

Nothing here names a cell, a configuration, a model or a driver: a cell is an
entry of ``workloads`` in ``BENCHMARK.json``, which names a configuration file
(sizes), a traffic file (which names its driver and gives its parameters) and
the chips it needs.  Traffic files, drivers (``drivers/<name>.py``) and
per-layer readers (``layer_metrics/<name>.py``) are all found by name, by the
one lookup ``_find``, under the directories of ``paths``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import inspect
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

Span = Tuple[float, float, float]  # first issue, last issue returned, last wait returned


# -- what a cell is ------------------------------------------------------------


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    search: List[str] = field(default_factory=lambda: [HERE])


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    search = search_dirs(root)
    traffic = _find(search, "traffic", w["traffic"], (".json",))
    return Cell(
        name=w["name"],
        chips=int(w["chips"]),
        config=_read_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=_read_json(traffic),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, w["name"])],
        per_layer=[m for m in bench["per_layer"] if _applies(m, w["name"])],
        search=search,
    )


def search_dirs(root: str = ROOT) -> List[str]:
    """The directories of ``paths``: where traffic, drivers and readers
    are looked for, and where a driver's own modules lie."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    return [os.path.join(root, p) for p in bench["paths"]]


def _find(search: List[str], kind: str, name: str, endings) -> str:
    for base in search:
        for ending in endings:
            path = os.path.join(base, kind, name + ending)
            if os.path.exists(path):
                return path
    raise FileNotFoundError(
        f"no {kind}/{name}{{{','.join(endings)}}} under {search}")


_modules: Dict[str, object] = {}   # by path: what _load has executed


def _load(search: List[str], kind: str, name: str):
    """The module ``<kind>/<name>.py``, found as a traffic file is and
    executed once in a process.  Every directory of ``search`` is importable
    from it, so a file brings its own reference, generator or least-bytes
    function as modules beside ``<kind>/``."""
    path = _find(search, kind, name, (".py",))
    module = _modules.get(path)
    if module is None:
        for base in search:
            if base not in sys.path:
                sys.path.append(base)
        spec = importlib.util.spec_from_file_location(
            kind + "_" + "".join(c if c.isalnum() else "_" for c in name),
            path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _modules[path] = module
    return module


def load_reader(search: List[str], name: str) -> Callable:
    """``read(ctx)`` of ``layer_metrics/<name>.py``."""
    return _load(search, "layer_metrics", name).read


# What ``_drive`` asks of a driver (``README.md``, "A driver"): constructed
# as ``Driver(cluster, config, traffic, seed)``; these it calls, these it
# reads.
DRIVER_CALLS = ("setup", "checked_steps", "step", "compare", "counters",
                "expected_counters", "least_bytes")
DRIVER_READS = ("payload_bytes_per_step", "steps_done", "tracing")


def load_driver(search: List[str], name: str) -> type:
    """The class ``Driver`` of ``drivers/<name>.py``.  One that lacks a
    member of the protocol is refused by name, here, before anything boots.
    A driver file that builds on another takes its class through this same
    call: ``harness.load_driver(harness.search_dirs(), "<its name>")``."""
    module = _load(search, "drivers", name)
    cls = getattr(module, "Driver", None)
    if not isinstance(cls, type):
        raise TypeError(f"{module.__file__} gives no class named Driver")
    lacks = [m for m in DRIVER_CALLS if not callable(getattr(cls, m, None))]
    lacks += [m for m in DRIVER_READS if not hasattr(cls, m)]
    try:
        inspect.signature(cls).bind("cluster", "config", "traffic", 0)
    except TypeError:
        lacks.insert(0, "__init__(cluster, config, traffic, seed)")
    if lacks:
        raise TypeError(
            f"driver {name!r} ({module.__file__}) lacks "
            f"{', '.join(lacks)}: what the harness asks of a driver is in "
            f"benchmark/README.md")
    return cls


def resolve(cell: Cell) -> type:
    """Find by file what the cell's data names as code, in a second and
    before anything boots (``run_cell`` starts with it): each per-layer
    reader, and the traffic's driver, whose class it returns."""
    for m in cell.per_layer:
        _find(cell.search, "layer_metrics", m["name"], (".py",))
    if "driver" not in cell.traffic:
        raise KeyError(f"the traffic file of cell {cell.name!r} names no "
                       f"driver")
    return load_driver(cell.search, cell.traffic["driver"])


# -- what a reader is given ----------------------------------------------------


@dataclass
class LayerContext:
    """Everything a per-layer reader may read.  A reader that finds nothing
    returns None and its metric is left out of the line."""

    spans: List[Span]              # the profiler-off window's steps
    compiles_in_window: int        # jax.monitoring events inside that window
    reduction: object              # trace_reduce.Reduction of the traced steps, or None
    least: Dict[str, float]        # least_bytes of one step, per device
    peaks: Dict[str, float]        # this device_kind's row of peaks.json
    config: dict = field(default_factory=dict)    # the cell's configuration file
    traffic: dict = field(default_factory=dict)   # the cell's traffic file
    # The traced section's ``jax.profiler.ProfileData`` (planes -> lines ->
    # events with their stats), for what ``reduction`` does not keep; None
    # where nothing was traced.  The files it was read from are gone.
    profile: object = None


# -- device ----------------------------------------------------------------------


class NoDevice(RuntimeError):
    """The machine is not what the cell asks for: no result is printed."""


def find_devices(chips: int, require_tpu: bool = True):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    peaks = _read_json(os.path.join(HERE, "peaks.json"))
    if require_tpu:
        if d0.platform != "tpu":
            raise NoDevice(f"JAX found platform {d0.platform!r}, not a TPU: "
                           f"the benchmark has no CPU mode")
        if d0.device_kind not in peaks:
            raise NoDevice(f"device_kind {d0.device_kind!r} is not in "
                           f"benchmark/peaks.json: add its published peaks "
                           f"with their source")
    if len(devices) != chips:
        raise NoDevice(f"the cell asks for {chips} chip(s), JAX found "
                       f"{len(devices)}")
    return devices, peaks.get(d0.device_kind, next(iter(peaks.values())))


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Compilations and compile-cache reads, through ``jax.monitoring``.
    A program that is built inside the window stalls a step whether it was
    compiled or read from the cache, so both count."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.count = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# -- the window ------------------------------------------------------------------


@dataclass
class Window:
    spans: List[Span] = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    start: float = 0.0
    end: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run_window(driver, seconds: float, deadline_s: float,
               max_steps: Optional[int] = None, min_steps: int = 0,
               annotate: bool = False) -> Window:
    """Closed loop: issue a step while the window is open (or, for a
    traced section, until ``max_steps``, and at least ``min_steps``)."""
    if annotate:
        import jax

        step_span = lambda: jax.profiler.TraceAnnotation("bench_step")
    else:
        step_span = contextlib.nullcontext
    w = Window(start=time.perf_counter())
    while True:
        now = time.perf_counter()
        over = now - w.start >= seconds or (
            max_steps is not None and w.attempted >= max_steps)
        if over and w.attempted >= min_steps:
            break
        w.attempted += 1
        try:
            with step_span():
                span = driver.step()
        except Exception:
            traceback.print_exc()
            w.failed += 1
            continue
        if span[2] - span[0] > deadline_s:
            w.failed += 1
        w.spans.append(span)
    w.end = w.spans[-1][2] if w.spans else time.perf_counter()
    return w


def traced_section(driver, traffic: dict, deadline_s: float):
    """A short steady section under the profiler, in a run of its own
    part: returns (window, reduction or None, the profile it was reduced
    from or None)."""
    import jax

    import trace_reduce

    spec = traffic.get("trace", {})
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    # Python frames of 467 calls a step would swamp the trace; the
    # benchmark's own annotations are host-tracer events.
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        driver.tracing = True
        try:
            w = run_window(driver, float(spec.get("seconds", 3.0)),
                           deadline_s,
                           max_steps=int(spec.get("max_steps", 20)),
                           min_steps=int(spec.get("min_steps", 5)),
                           annotate=True)
        finally:
            driver.tracing = False
            jax.profiler.stop_trace()
        profile = trace_reduce.load(trace_dir)
        reduction = (trace_reduce.reduce_trace(profile)
                     if profile is not None else None)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return w, reduction, profile


def _clock_line(r) -> str:
    """How the device's timeline was laid on the host's, for the ``trace:``
    line of every traced run."""
    if r.clock != "spans":
        return (f"clock {r.clock} (the first operation drawn to the first "
                f"issue: {r.clock_note}), offset {r.clock_offset_ns:.0f} ns")
    idle_ns = (r.window_s - r.busy_s) * 1e9 / r.steps
    wide = (", WIDE: over a fifth of the idle time a step"
            if r.clock_bracket_ns > idle_ns / 5 else "")
    return (f"clock by the program's spans (a launch told by "
            f"{r.clock_note}), offset {r.clock_offset_ns:.0f} ns, bracket "
            f"{r.clock_bracket_ns:.0f} ns of {idle_ns:.0f} ns idle a "
            f"step{wide}")


# -- one run ---------------------------------------------------------------------


def _expired(seconds: float) -> None:
    """A hung device call cannot be interrupted from Python."""
    print(f"benchmark: no end after {seconds:.0f} s", file=sys.stderr,
          flush=True)
    sys.stdout.flush()
    os._exit(3)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             control: Optional[str] = None,
             limit_s: float = 1150.0) -> Tuple[bool, dict]:
    """Run the cell once and return (ok, result line as a dict).  Raises
    :class:`NoDevice` before any work where the machine is not what the
    cell asks for.  ``control`` (``benchmark/readings.py`` only) also reads
    the lower-precision control's numbers, printed on earlier lines."""
    from boot import Cluster

    driver_class = resolve(cell)
    watchdog = threading.Timer(limit_s, _expired, args=(limit_s,))
    watchdog.daemon = True
    watchdog.start()
    try:
        devices, peaks = find_devices(cell.chips, require_tpu)
        from pslite_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        compiles = CompileCounter()
        t_boot = time.perf_counter()
        cluster = Cluster(cell.config["server_handle"])
        try:
            return _drive(cell, driver_class, cluster, devices, peaks,
                          compiles, cache_dir, seed, seconds, trace, t_start,
                          t_boot, control)
        finally:
            cluster.shutdown()
    finally:
        watchdog.cancel()


def _drive(cell, driver_class, cluster, devices, peaks, compiles, cache_dir,
           seed, seconds, trace, t_start, t_boot, control):
    import reference
    from least_bytes import least_seconds

    traffic = cell.traffic
    deadline_s = float(traffic.get("step_deadline_s", 30.0))
    driver = driver_class(cluster, cell.config, traffic, seed)
    t0 = time.perf_counter()
    parts = driver.setup()
    t1 = time.perf_counter()
    driver.checked_steps()
    t2 = time.perf_counter()
    for _ in range(int(traffic.get("warm_steps", 2))):
        driver.step()
    t3 = time.perf_counter()
    print(f"set-up: to boot {t_boot - t_start:.2f} s, boot "
          f"{t0 - t_boot:.2f} s, registration {parts['register']:.2f} s, inputs "
          f"{parts['inputs']:.2f} s, "
          f"checked steps (compile) {t2 - t1:.2f} s, warm steps "
          f"{t3 - t2:.2f} s; compile cache {cache_dir}: {compiles.hits} "
          f"hits, {compiles.misses} misses", flush=True)

    counters0 = driver.counters()
    steps0 = driver.steps_done
    compiles0 = compiles.count
    window = run_window(driver, seconds, deadline_s)
    compiles_in_window = compiles.count - compiles0
    setup_s = window.start - t_start
    counters1 = driver.counters()
    steps_counted = driver.steps_done - steps0

    attempted, failed = window.attempted, window.failed
    reduction = None
    if trace:
        traced, reduction, profile = traced_section(driver, traffic,
                                                    deadline_s)
        attempted += traced.attempted
        failed += traced.failed

    durations = np.array([s[2] - s[0] for s in window.spans]) * 1e3
    print(f"window: {len(window.spans)} steps completed of "
          f"{window.attempted} issued in {window.seconds:.3f} s "
          f"({failed} failed), {driver.payload_bytes_per_step:,} payload "
          f"bytes a step, {compiles_in_window} compilations in the window",
          flush=True)
    if window.spans:
        # Where a run reads far off, this says whether a few steps stalled
        # (and when) or every step was slower.
        slowest = sorted(range(len(durations)), key=lambda i: -durations[i])
        print("window: slowest steps "
              + ", ".join(f"#{i} {durations[i]:.1f} ms" for i in slowest[:3])
              + f"; mean {durations.mean():.3f} ms, median "
              f"{np.median(durations):.3f} ms", flush=True)

    t4 = time.perf_counter()
    comparisons = driver.compare()
    want = driver.expected_counters(steps_counted)
    got = tuple(b - a for a, b in zip(counters0, counters1))
    comparisons.append(("engine_byte_counters_gap",
                        float(sum(abs(g - w) for g, w in zip(got, want))),
                        0.0))
    correct = bool(window.spans)
    for name, value, limit in comparisons:
        ok = bool(value <= limit)
        correct = correct and ok
        print(f"compare {name}: {value!r} limit {limit!r} "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)
    if control:
        for name, value, limit in driver.compare(
                getattr(reference, control)):
            print(f"control[{control}] {name}: {value!r} limit {limit!r} "
                  f"{'fails, as it must' if value > limit else 'PASSES'}",
                  flush=True)
    print(f"reference and comparison: {time.perf_counter() - t4:.2f} s "
          f"(outside set-up and window)", flush=True)

    metrics: Dict[str, dict] = {}
    if not trace:
        values = {"setup_s": setup_s}
        if window.spans:
            values.update(
                goodput=(driver.payload_bytes_per_step * len(window.spans)
                         / window.seconds / 1e9),
                step_p50=float(np.percentile(durations, 50)),
                step_p95=float(np.percentile(durations, 95)),
            )
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = LayerContext(
            spans=window.spans, compiles_in_window=compiles_in_window,
            reduction=reduction, least=driver.least_bytes(), peaks=peaks,
            config=cell.config, traffic=traffic, profile=profile)
        least = least_seconds(ctx.least, peaks)
        print(f"least time of a step on this chip: "
              f"{least['seconds'] * 1e3:.3f} ms, bound by {least['bound']}",
              flush=True)
        if reduction is not None:
            print(f"trace: {reduction.steps} steps on {reduction.devices} "
                  f"device(s), launches repeat exactly: "
                  f"{reduction.launches_repeat}; {_clock_line(reduction)}",
                  flush=True)
        for m in cell.per_layer:
            value = load_reader(cell.search, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(devices)}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = {"device_ops": reduction.device_ops,
                               "idle_gaps": reduction.idle_gaps}
    return correct, result
