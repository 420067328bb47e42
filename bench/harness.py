"""The benchmark's one entry: find the cell, hold the device to the peaks
table, set up, measure, check, print.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  the configuration as it is run;
* ``bench/traffic/<mix>.json``     the mix's parameters and its ``kind``;
* ``bench/drivers/<kind>.py``      ``setup``, ``window``, ``release``,
                                   ``checks`` for that kind of traffic,
                                   and the ``SPANS`` it records;
* ``bench/metrics/<metric>.py``    ``read(run)`` -> value or None;
* ``bench/limits/<cell>.json``     the limits ``correct`` is held to.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, workload: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])
             and m["moves"] in names]
    here = root / "bench"
    lim_path = here / "limits" / f"{workload}.json"
    return Cell(name=workload, chips=w["chips"],
                config=load_json(root / conf["file"]),
                traffic=load_json(here / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(lim_path)["limits"] if lim_path.exists()
                else {},
                end_to_end=e2e, per_layer=layer)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def process_age_s(fallback_start: float) -> float:
    """Seconds since this process started (from /proc where it exists)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - fallback_start


class Spans:
    """Host spans around the calls the harness makes into each layer,
    written into the profiler's trace while one runs."""

    def __init__(self):
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


class Tracer:
    """Traces ``trace_s`` seconds from the middle of the window; idle
    gaps are named by the driver's host ``spans``."""

    def __init__(self, run, trace_s: float, spans: tuple):
        self.run = run
        self.trace_s = trace_s
        self.spans = spans
        self.dir = None
        self.t_start = self.t_stop = None
        self._ann = None

    def plan(self, t_open: float, seconds: float) -> None:
        lead = max(0.0, (seconds - self.trace_s) / 2)
        self.t_start = t_open + lead
        self.t_stop = self.t_start + self.trace_s

    def tick(self, now: float) -> None:
        import jax
        if self.t_start is None:           # the window has not opened
            return
        if self.dir is None and now >= self.t_start:
            self.dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self.dir)
            self.run.spans.annotate = True
            self._ann = jax.profiler.TraceAnnotation("window")
            self._ann.__enter__()
            self.t_start = time.perf_counter()
        elif self._ann is not None and now >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        import jax
        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        self.t_stop = time.perf_counter()
        self.run.spans.annotate = False
        jax.profiler.stop_trace()

    def reduce(self):
        from bench import trace
        try:
            return trace.reduce(trace.find_xplane(self.dir), self.spans)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Run:
    """One run: what the driver and the metric readers share."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    peak: dict
    spans: Spans = field(default_factory=Spans)
    state: dict = field(default_factory=dict)
    counters: dict = field(default_factory=dict)
    e2e: dict = field(default_factory=dict)
    steps: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    tracer: Tracer = None
    reduced: object = None         # bench.trace.Reduced of the traced run
    setup_s: float = None          # process start to the window's open
    on_open: object = None         # the harness's hook at the window's open
    on_close: object = None

    @property
    def limits(self) -> dict:
        return self.cell.limits

    def log(self, msg: str) -> None:
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def open_window(self, t_open: float) -> None:
        """The driver calls this once, as its measured window opens."""
        if self.on_open is not None:
            self.on_open()
        if self.tracer is not None:
            self.tracer.plan(t_open, self.seconds)

    def tick(self, now: float) -> None:
        if self.tracer is not None:
            self.tracer.tick(now)

    def close_window(self, t_close: float) -> None:
        if self.tracer is not None:
            self.tracer.stop()
        if self.on_close is not None:
            self.on_close()

    def traced(self, t0: float, t1: float) -> bool:
        """Whether a host interval lies inside the traced window."""
        tr = self.tracer
        return tr is not None and tr.t_start is not None \
            and tr.t_start <= t0 and t1 <= tr.t_stop


def device_or_error(chips: int, peaks: dict, require_tpu: bool):
    """The devices this run uses and the peaks row of their kind, or an
    error message."""
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return None, None, f"no TPU found (JAX platform " \
                           f"{devices[0].platform!r})"
    if len(devices) < chips:
        return None, None, f"the cell needs {chips} chips, found " \
                           f"{len(devices)}"
    kind = devices[0].device_kind
    if kind not in peaks:
        if require_tpu:
            return None, None, f"device kind {kind!r} is not in peaks.json"
        return devices[:chips], {"bf16_flops": float("nan"),
                                 "hbm_bytes_per_s": float("nan")}, None
    return devices[:chips], peaks[kind], None


class CompileCounter:
    """Counts backend compiles while ``active`` (none belong in a window)."""

    def __init__(self):
        import jax
        self.active = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and "backend_compile" in event:
            self.n += 1


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


TRACE_S = 4.0


def build(root, args, require_tpu: bool = True):
    """Load the cell, hold the device to the peaks table; returns
    (run, driver, devices) or raises SystemExit with a message."""
    root = Path(root)
    cell = load_cell(root, args.workload)
    peaks = load_json(root / "bench" / "peaks.json")["devices"]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices, peak, err = device_or_error(cell.chips, peaks, require_tpu)
    if err:
        raise SystemExit(f"bench: {err}")
    driver = load_module(root / "bench" / "drivers" /
                         f"{cell.traffic['kind']}.py",
                         f"bench_driver_{cell.traffic['kind']}")
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), peak=peak)
    if run.trace:
        run.tracer = Tracer(run, min(TRACE_S, args.seconds), driver.SPANS)
    return run, driver, devices


def is_correct(checks: list) -> bool:
    """Every reading is there and within its limit."""
    return all(v is not None and v <= lim for _, v, lim in checks)


def execute(root, run, driver, devices, start: float,
            control: bool = False) -> dict:
    """Set up, measure, check; returns the result object.  ``control``
    puts the control in the program's place in the checks."""
    root = Path(root)
    compiles = CompileCounter()

    def on_open():
        run.setup_s = process_age_s(start)
        compiles.active = True

    def on_close():
        compiles.active = False
    run.on_open, run.on_close = on_open, on_close
    driver.setup(run)
    # what set-up made is kept for the run: the collector leaves it alone
    gc.collect()
    gc.freeze()
    try:
        driver.window(run)
    finally:
        gc.unfreeze()
    setup_s = run.setup_s
    run.log(f"compiles inside the window: {compiles.n}")
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    if run.tracer is not None:
        run.reduced = run.tracer.reduce()
    driver.release(run)
    checks = driver.checks(run, control)
    correct = is_correct(checks)

    metrics = {}
    if run.trace:
        for m in run.cell.per_layer:
            reader = load_module(root / "bench" / "metrics" /
                                 f"{m['name']}.py", f"bench_metric_{m['name']}")
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        run.e2e["setup_s"] = setup_s
        for m in run.cell.end_to_end:
            v = run.e2e.get(m["name"])
            if v is None:
                raise RuntimeError(f"the driver gave no {m['name']}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if run.reduced is not None:
        device.update(busy_s=run.reduced.busy_s,
                      window_s=run.reduced.window_s)
        result["breakdown"] = {"device_ops": run.reduced.top_ops(),
                               "idle_gaps": run.reduced.top_gaps()}
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    run.log(f"setup_s {setup_s:.3f}")
    for n, v, lim in checks:
        print(f"check {n}: {v} (limit {lim})", file=sys.stderr, flush=True)
    return result


def main(argv, root, start: float, require_tpu: bool = True,
         control: bool = False) -> int:
    args = parse(argv)
    try:
        run, driver, devices = build(root, args, require_tpu)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 1
    result = execute(root, run, driver, devices, start, control)
    print(json.dumps(result), flush=True)
    return 0
