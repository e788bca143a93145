"""The benchmark's harness: manifest, device gate, window, trace, result.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by name:

* ``bench/configs/<config>.json``: the deployment's sizes and source;
* ``bench/traffic/<traffic>.json``: the mix's parameters, naming the
  entry (``bench/entries/<entry>.py``) that drives the served path and
  the generator (``bench/gen/<generator>.py``) it draws inputs from;
* ``bench/layer_metrics/<metric>.py``: ``read(run)`` reduces the window's
  counters or device trace to one number, or returns None when it finds
  nothing to read.

An entry's ``run(ctx)`` builds its inputs from ``ctx.seed``, warms every
shape it will use, calls ``ctx.open_window()``, drives the program for
``ctx.seconds``, calls ``ctx.close_window()`` and only then runs the
reference. It returns an ``Outcome``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
T_START = time.perf_counter()

# events of a program being traced, lowered, compiled or fetched from the
# persistent cache; the last two carry the time set-up spends on compiling
_TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def manifest(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    """One workload of the manifest with its configuration and traffic."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # the manifest's end-to-end metrics this cell reports
    per_layer: list  # the per-layer metrics this cell reports

    @property
    def entry(self):
        return load_module(BENCH / "entries" / f"{self.traffic['entry']}.py",
                           f"bench_entry_{self.traffic['entry']}")

    def generator(self):
        g = self.traffic["generator"]
        return load_module(BENCH / "gen" / f"{g}.py", f"bench_gen_{g}")


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, man: dict | None = None) -> Cell:
    man = manifest() if man is None else man
    wl = next((w for w in man["workloads"] if w["name"] == name), None)
    if wl is None:
        raise SystemExit(f"unknown workload {name!r}; the manifest has "
                         f"{[w['name'] for w in man['workloads']]}")
    return build_cell(wl, man)


def build_cell(wl: dict, man: dict) -> Cell:
    """The cell of one ``workloads`` entry, with the manifest's metrics
    that it reports."""
    name = wl["name"]
    conf = next(c for c in man["configs"] if c["name"] == wl["config"])
    e2e = [m for m in man["end_to_end"] if reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"]
             if m["moves"] in e2e_names and reports(m, name)]
    return Cell(
        name, wl["chips"],
        json.loads((ROOT / conf["file"]).read_text()),
        json.loads((BENCH / "traffic" / f"{wl['traffic']}.json").read_text()),
        e2e, layer,
    )


def device_gate(chips: int):
    """The devices of a run: every chip the cell asks for, all TPUs.
    Exits non-zero, before anything is built, otherwise."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileWatch:
    """Seconds spent compiling programs or fetching them from the cache,
    and how many programs were traced or compiled inside the window."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.in_window = 0
        self.window = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if self.window and event in _COMPILE_EVENTS + _TRACE_EVENTS:
            self.in_window += 1


@dataclasses.dataclass
class Outcome:
    """What an entry hands back: requests, calls or steps ``attempted`` in
    the window and how many ``failed``; its end-to-end metrics by name;
    counters for the per-layer readers; and each compared number as
    ``(name, value, limit)``, correct when every value is at most its
    limit."""

    attempted: int
    failed: int
    metrics: dict
    counters: dict
    checks: list


class Ctx:
    """What an entry sees of the harness."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices, watch: CompileWatch | None = None):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.watch = watch
        self.setup_s = None
        self.window_s = None
        self.memory_peak = None
        self.trace_dir = None
        self._t0 = None

    log = staticmethod(log)

    def open_window(self) -> float:
        import jax

        if self.watch is not None:
            log(f"bench: compile_s {self.watch.seconds!r} during set-up")
            self.watch.window = True
        if self.trace:
            import tempfile

            self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.trace_dir)
        self._t0 = time.perf_counter()
        self.setup_s = self._t0 - T_START
        return self._t0

    def close_window(self, t_end: float | None = None) -> float:
        """Ends the window (at ``t_end`` if the entry timed its own end);
        stops the trace and reads the devices' peak memory."""
        import jax

        t1 = time.perf_counter() if t_end is None else t_end
        self.window_s = t1 - self._t0
        if self.trace:
            jax.profiler.stop_trace()
        if self.watch is not None:
            self.watch.window = False
            log(f"bench: compilations_in_window {self.watch.in_window}")
        self.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices
        )
        return t1

    @staticmethod
    def span(name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def key(self):
        """A JAX PRNG key that uses every bit of the seed."""
        import jax

        k = jax.random.key(self.seed & 0xFFFFFFFF)
        return jax.random.fold_in(k, (self.seed >> 32) & 0x7FFFFFFF)


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


@dataclasses.dataclass
class Run:
    """What a per-layer reader sees: the cell, the window, the entry's
    counters, the reduced device trace and the chip's peaks."""

    cell: Cell
    window_s: float
    counters: dict
    trace: object  # bench.trace.Reduced or None
    peaks: dict
    chips: int


def layer_metrics(run: Run) -> dict:
    out = {}
    for m in run.cell.per_layer:
        reader = load_module(BENCH / "layer_metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def execute(c: Cell, seed: int, seconds: float, trace: bool, devices,
            watch: CompileWatch | None = None) -> dict:
    """Run one cell once; returns the result line's object."""
    ctx = Ctx(c, seed, seconds, trace, devices, watch)
    out: Outcome = c.entry.run(ctx)
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": ctx.memory_peak}
    if trace:
        from bench import trace as tr

        red = tr.reduce_dir(ctx.trace_dir, [x.id for x in devices],
                            ctx.window_s)
        run = Run(c, ctx.window_s, out.counters, red, peaks(d.device_kind),
                  len(devices))
        metrics = layer_metrics(run)
        device["busy_s"] = red.busy_s
        device["window_s"] = ctx.window_s
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.idle_gaps(10)}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": out.metrics[m["name"]],
                               "unit": m["unit"]} for m in c.end_to_end
                   if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
        breakdown = None
    correct = all(v <= lim for _, v, lim in out.checks) and bool(out.checks)
    line = {"correct": correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in out.checks}
    return line
