"""What every cell shares: looking a cell up by name, the device and
its peaks, counting compilations, the traced window, the per-layer
readers and the result line. Data-driven: a cell is an entry of
`BENCHMARK.json` naming a file under `configs/` and one under
`traffic/`; the mix names its driver under `drivers/`; a per-layer
metric is `layer_metrics/<name>.json` (the name of a reader module and
its parameters) or `layer_metrics/<name>.py` (a reader of its own).
Nothing here names a cell, a mix or a metric.
"""

from __future__ import annotations

import glob
import importlib
import importlib.util
import json
import os
import os.path as osp
import shutil
import statistics
import threading
import time

HERE = osp.dirname(osp.abspath(__file__))
ROOT = osp.dirname(HERE)
OUT_DIR = osp.join(ROOT, ".bench_out")  # git-ignored run-time files


def load_json(*parts: str, base: str = HERE) -> dict:
    with open(osp.join(base, *parts)) as fp:
        return json.load(fp)


def load_benchmark(root: str = ROOT) -> dict:
    with open(osp.join(root, "BENCHMARK.json")) as fp:
        return json.load(fp)


def load_cell(name: str, bench: dict | None = None, *,
              base: str = HERE) -> dict:
    """The cell `name` with its configuration and traffic mix read in
    (`base` is the benchmark's directory)."""
    bench = bench or load_benchmark(osp.dirname(base))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    cell = dict(cells[name])
    cell["config_data"] = load_json(
        "configs", cell["config"] + ".json", base=base)
    cell["mix"] = load_json("traffic", cell["traffic"] + ".json", base=base)
    return cell


def merge(base: dict, over: dict) -> dict:
    """`base` with `over` laid on it, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


def load_driver(name: str):
    return importlib.import_module(f"benchmarks.drivers.{name}")


# ---------------------------------------------------------------------------
# checks: every number compared, beside its limit
# ---------------------------------------------------------------------------

_OPS = {
    "<=": lambda v, lim: v <= lim,
    ">=": lambda v, lim: v >= lim,
    "==": lambda v, lim: v == lim,
}


def check(name: str, value, limit, op: str = "<=") -> dict:
    """One compared number. A value that is not a number (None, nan)
    fails."""
    try:
        ok = bool(_OPS[op](value, limit)) and value == value
    except TypeError:
        ok = False
    return {"check": name, "value": value, "op": op, "limit": limit,
            "ok": ok}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

REQUIRED_PLATFORM = "tpu"


def device_info(chips: int) -> tuple[dict, dict]:
    """`device` block of the result line and the device's peaks. Fails
    where the backend is not a TPU, holds fewer chips than the cell
    asks for, or is of a kind `peaks.json` does not list."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if info["platform"] != REQUIRED_PLATFORM or info["count"] < chips:
        raise SystemExit(
            f"need {chips} {REQUIRED_PLATFORM} device(s); jax.devices() "
            f"gives {info}")
    peaks = load_json("peaks.json")["devices"]
    if info["kind"] not in peaks:
        raise SystemExit(
            f"device kind {info['kind']!r} is not in benchmarks/peaks.json "
            f"({sorted(peaks)}); add it with its source")
    return info, peaks[info["kind"]]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number (the driver's
    seeds pass 2**31)."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def seed31(seed: int) -> int:
    """`seed` folded into what a signed 32-bit config field holds."""
    return seed % 0x7FFFFFFF


def enable_compile_cache() -> str:
    """The program's own cache set-up (`JAX_COMPILATION_CACHE_DIR`, else
    `<checkout>/.jax_cache`), with every program kept, however short
    its compile: a warm run loads them all."""
    import jax

    from sparksched_tpu import config

    config.enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or jax.config.jax_compilation_cache_dir)


class CompileCounter:
    """Counts programs lowered or compiled while `armed`. A bare
    `jaxpr_trace_duration` is not one: under rbg keys an eager
    `fold_in` re-traces a helper on the host at every call and
    compiles nothing (PERF.md, PR 25)."""

    EVENTS = ("jaxpr_to_mlir_module_duration", "backend_compile_duration")

    def __init__(self) -> None:
        import jax

        self.armed = False
        self.count = 0
        self.seconds_total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith(self.EVENTS):
            if event.endswith("backend_compile_duration"):
                self.seconds_total += float(duration)
            if self.armed:
                self.count += 1


# ---------------------------------------------------------------------------
# the traced window
# ---------------------------------------------------------------------------


WINDOW_SPAN = "bench/trace_window"


class Tracer:
    """A profiler trace of part of the window, reduced after the window.
    `host_spans` are the names of the `TraceAnnotation`s the driver
    writes around its calls into the program; idle gaps are named by
    them."""

    def __init__(self, cell_name: str, chips: int,
                 host_spans: tuple[str, ...], unattributed: str,
                 scopes: tuple[str, ...] = ()) -> None:
        self.dir = osp.join(OUT_DIR, cell_name, "trace")
        self.chips = chips
        self.host_spans = host_spans
        self.unattributed = unattributed
        self.scopes = scopes  # looked for besides the reducer's own
        self.running = False

    def start(self) -> None:
        """Starts the profiler (no Python-call events: they swell the
        trace and slow the host) and opens the host span that marks the
        traced window. Call `start` and `stop` from one thread."""
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._window.__enter__()
        self.running = True

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def reduce(self) -> dict:
        from benchmarks import trace_reduce

        paths = glob.glob(
            osp.join(self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace to {self.dir}")
        reduced = trace_reduce.reduce_file(
            paths[0], chips=self.chips, host_spans=self.host_spans,
            unattributed=self.unattributed, window_span=WINDOW_SPAN,
            scopes=trace_reduce.scope_names(self.scopes))
        shutil.rmtree(self.dir, ignore_errors=True)  # hundreds of MB
        return reduced


def trace_for(tracer: Tracer | None, start_s: float, seconds: float):
    """Traces `seconds` of the window from `start_s` on, from a thread
    of its own, while the caller drives the window: a trace of a whole
    collection is millions of events, of which the device's buffer
    drops most, and minutes of serialising. The caller ends its window
    on time and then joins the thread it gets (stopping the profiler
    takes seconds, longer while the device is busy) before reducing;
    None without a tracer."""
    if tracer is None:
        return None

    def run() -> None:
        time.sleep(start_s)
        tracer.start()
        time.sleep(seconds)
        tracer.stop()

    thread = threading.Thread(target=run, name="bench-trace")
    thread.start()
    return thread


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------


def metrics_of_cell(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The entries of `end_to_end` or `per_layer` that this cell
    reports: those listing it, and those with no `workloads` key."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def metric_scopes(base: str = HERE) -> tuple[str, ...]:
    """Every `scope` value of the per-layer metrics' data files, sorted:
    the names a traced run's reducer looks for besides its own, so a
    metric over a scope that no list has is its data file alone."""
    specs = (load_json(path, base="") for path in glob.glob(
        osp.join(base, "layer_metrics", "*.json")))
    return tuple(sorted({s["scope"] for s in specs if s.get("scope")}))


def _load_file(path: str):
    """The module in the file `path` (a metric's name may hold dots, and
    a later PR's reader sits wherever its benchmark directory does)."""
    name = "benchmarks.layer_metrics._file_" + "".join(
        c if c.isalnum() else "_" for c in osp.basename(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_layer_metric(name: str, window: dict, *, base: str = HERE):
    """The per-layer metric `name` from what the window recorded, or
    None where its reader finds nothing to read."""
    own = osp.join(base, "layer_metrics", name + ".py")
    if osp.exists(own):
        return _load_file(own).read(window)
    spec = load_json("layer_metrics", name + ".json", base=base)
    params = {k: v for k, v in spec.items() if k not in ("reader", "why")}
    reader = _load_file(
        osp.join(base, "layer_metrics", spec["reader"] + ".py"))
    return reader.read(window, **params)


def stat(values, how: str):
    """median, mean or max of a list (None if it is empty)."""
    values = [float(v) for v in values]
    if not values:
        return None
    if how == "median":
        return statistics.median(values)
    if how == "mean":
        return statistics.fmean(values)
    if how == "max":
        return max(values)
    raise ValueError(f"unknown statistic {how!r}")


# ---------------------------------------------------------------------------
# output
# ---------------------------------------------------------------------------


def say(**fields) -> None:
    """An earlier line of standard output (JSON; the driver reads only
    the last)."""
    print(json.dumps(fields, default=plain), flush=True)


def plain(o):
    try:
        return o.item()
    except AttributeError:
        return str(o)
