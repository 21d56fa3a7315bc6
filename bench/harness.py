"""The harness: one run of one cell, driven by ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``, whose
``system`` key picks the driver ``bench/systems/<system>.py``) and a traffic
mix (``bench/traffic/<traffic>.json``); each metric is read by
``bench/metrics/<metric>.py``.  Adding a cell adds files and a ``workloads``
entry; no file here changes.

A run: set-up (compile cache, device check, the cell built from the seed and
warmed up on its own shapes), one measured window of ``--seconds``, the
device's memory peak, then the comparison with the plain reference on what
the window produced.  The last line of standard output is the result.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(REPO / "src"))


class NoDevice(SystemExit):
    """The run cannot be measured here: no result is printed."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


# ---------------------------------------------------------------------------
# lookup by name
# ---------------------------------------------------------------------------
def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    path = REPO / "BENCHMARK.json"
    if not path.is_file():
        raise NoDevice(f"no {path.name} at {REPO}")
    return load_json(path)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def cell_metrics(bm: dict, workload: str, trace: bool) -> list[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics,
    or with ``trace`` its per-layer ones."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py").read


def system(name: str):
    return load_module(BENCH / "systems" / f"{name}.py")


# ---------------------------------------------------------------------------
# JAX process set-up and measurement helpers
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts programs lowered and compiled in this process (JAX monitoring
    events), so a window can report what it compiled: it should be 0."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.COMPILE:
            self.compiled += 1

    def total(self) -> int:
        return self.lowered + self.compiled


def device_info() -> dict:
    from repro.runtime.jax_env import device_info as info

    return info()


def setup_jax(chips: int) -> dict:
    """Compile cache first, then the device check.  Returns the device."""
    import jax

    from repro.runtime.jax_env import enable_compile_cache

    enable_compile_cache()
    # every program of the cell goes to the cache, so that only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = device_info()
    if dev["platform"] != "tpu":
        raise NoDevice(f"no TPU: JAX found {dev['platform']!r} "
                       f"({dev['kind']})")
    if dev["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{dev['count']}")
    return dev


def memory_peak_bytes():
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def spans(tracing: bool):
    """``span(name)``: a host span in the profiler's trace when tracing, a
    no-op otherwise."""
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


@contextlib.contextmanager
def traced(enabled: bool):
    """Profile the block into a temporary directory; yields a holder whose
    ``trace`` is the reduced :class:`xplane.Trace` once the block ends."""
    holder = type("Traced", (), {"trace": None})()
    if not enabled:
        yield holder
        return
    import jax

    import xplane

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tmp:
        jax.profiler.start_trace(tmp)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield holder
        finally:
            jax.profiler.stop_trace()
        paths = sorted(pathlib.Path(tmp).glob("**/*.xplane.pb"))
        if paths:
            holder.trace = xplane.Trace(str(paths[-1]))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, small: tuple | None = None) -> dict:
    """One run of one cell; returns the result object (not yet printed).

    ``small``, a (workloads entry, configuration, mix) triple, stands for
    the named cell: the tests' small sizes, run on whatever device JAX has,
    with no device check and no compile cache.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    bm = benchmark()
    if small is None:
        wl = find(bm["workloads"], workload, "workload")
        cfg, mix = config(wl["config"]), traffic(wl["traffic"])
        device = setup_jax(wl["chips"])
    else:
        wl, cfg, mix = small
        device = device_info()
    metrics = cell_metrics(bm, workload, trace)

    counter = CompileCounter()
    cell = system(cfg["system"]).Cell(cfg, mix, seed, counter=counter)
    setup_s = time.perf_counter() - t_start

    if trace:
        # a profiled window is short: the trace is read back in the run
        seconds = min(seconds, float(mix.get("trace_seconds", seconds)))
    before = counter.total()
    with traced(trace) as tr:
        rec = cell.window(seconds, spans(trace))
    in_window = counter.total() - before
    print(f"bench: {workload} seed {seed}: programs lowered or compiled "
          f"inside the window: {in_window}", file=sys.stderr, flush=True)
    if rec.round_s:
        report_rounds(rec)

    device["memory_peak_bytes"] = memory_peak_bytes()
    rec.setup_s = setup_s
    rec.trace = tr.trace
    rec.device_kind = device["kind"]
    cell.free()
    checks = cell.check(rec)
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and rec.failed == 0

    values = {}
    for m in metrics:
        v = reader(m["name"])(rec)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": correct, "attempted": rec.attempted,
           "failed": rec.failed, "metrics": values, "device": device}
    if trace and rec.trace is not None:
        t = rec.trace
        device["busy_s"] = t.busy_s
        device["window_s"] = t.window_s
        out["breakdown"] = {"device_ops": t.top_ops(10),
                            "idle_gaps": t.idle_gaps(10)}
    out["checks"] = checks
    return out


def report_rounds(rec):
    """How the window's time divides between rounds: the rate follows the
    mean round, the tail its 95th percentile, so rare long rounds (stalls)
    move the one and not the other."""
    import statistics

    rs = sorted(rec.round_s)
    med = statistics.median(rs)
    slow = [x for x in rs if x > 2 * med]
    print(f"bench: {len(rs)} rounds: median {med * 1e3:.4f} ms, mean "
          f"{statistics.fmean(rs) * 1e3:.4f} ms, max {rs[-1] * 1e3:.3f} ms; "
          f"{len(slow)} over twice the median take {sum(slow):.4f} s; "
          f"{rec.window_s - sum(rs):.4f} s of the window lies between rounds",
          file=sys.stderr, flush=True)


def report(out: dict):
    """Each compared number beside its limit as the last lines of standard
    error; the result as the last line of standard output."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"check failed segments: {out['failed']} of {out['attempted']} "
          f"(limit 0)", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
