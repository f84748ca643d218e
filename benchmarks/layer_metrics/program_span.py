"""One part of the run's set-up, in seconds (or a count), from the
program's own record of host spans and of jax's compile events
(`sparksched_tpu.obs.tracing.RECORD`, on `time.perf_counter()`).

Set-up runs from `benchmarks/run.py`'s `T0` (found as
`sys.modules["__main__"].T0`; absent, as under pytest, there is nothing
to read) to the start of the `collect/call` span that is the window's
first collection (`window["scalars"][0]["collection"]`, counted from 0
over the process's `collect/call` spans: the warm-up collections come
first). Four parts and what is left tile it:

- `before_trainer_s`: `T0` to the start of the first of `setup/mesh`
  and `setup/trainer_init`;
- `trainer_init_s`: `setup/mesh`, `setup/trainer_init` (its children
  inside it) and `setup/init_state`, summed;
- `collector_call_s`: the warm-up collections' `collect/call` spans,
  summed: the host's part, before the device has a program;
- `warmup_run_s`: from each of those spans' end to the next span's start
  or set-up's end: the device's run and the driver's reading of it;
- `unattributed_s`: set-up's length less the four.

`span_s` is one span's seconds within set-up, summed over its
occurrences (`"span": "setup/scheduler_init"`: a child of
`setup/trainer_init` that takes over a second on the chip).

Inside them, every function counted: `trace_s`, `lower_s` and
`compile_or_load_s` are the UNION of jax's `jaxpr_trace_duration`,
`jaxpr_to_mlir_module_duration` and `backend_compile_duration`
intervals within set-up (trace events nest, so a sum would count a
helper's trace twice; a backend compile wraps the cache's read, so on a
hit it is the load), and `cache_misses` counts the programs compiled
and written to the persistent cache: 0 on a warm run.

A program without the record (the parent commit of the PR that brought
it) has nothing to read: None where the data file says so
(`"may_lack": true`), an error otherwise.
"""

import sys

EVENTS = {
    "trace_s": "/jax/core/compile/jaxpr_trace_duration",
    "lower_s": "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "compile_or_load_s": "/jax/core/compile/backend_compile_duration",
}
CACHE_MISSES = "/jax/compilation_cache/cache_misses"
TRAINER_SPANS = ("setup/mesh", "setup/trainer_init", "setup/init_state")
CALL_SPAN = "collect/call"


def union_s(intervals, lo: float, hi: float) -> float:
    """Length of the union of `(start, end)` intervals within
    `[lo, hi]`."""
    total, reach = 0.0, lo
    for start, end in sorted(intervals):
        start, end = max(start, reach), min(end, hi)
        if end > start:
            total += end - start
            reach = end
    return total


def split(spans: list[dict], events: list[dict], t0: float,
          first_collection: int) -> dict | None:
    """Every part of set-up by name, or None where the record does not
    hold set-up's end or the trainer's start."""
    spans = sorted((s for s in spans if s["start"] >= t0),
                   key=lambda s: s["ordinal"])
    calls = [s for s in spans if s["name"] == CALL_SPAN]
    if not 0 <= first_collection < len(calls):
        return None
    end = calls[first_collection]["start"]
    spans = [s for s in spans if s["start"] < end]
    trainer = [s for s in spans if s["name"] in TRAINER_SPANS]
    if not trainer:
        return None
    parts = {
        "before_trainer_s": trainer[0]["start"] - t0,
        "trainer_init_s": sum(s["end"] - s["start"] for s in trainer),
        "collector_call_s": 0.0,
        "warmup_run_s": 0.0,
    }
    for call in calls[:first_collection]:
        parts["collector_call_s"] += call["end"] - call["start"]
        then = min([s["start"] for s in spans
                    if s["start"] >= call["end"]] + [end])
        parts["warmup_run_s"] += then - call["end"]
    parts["unattributed_s"] = (end - t0) - sum(parts.values())
    parts["span_s"] = {}
    for s in spans:
        parts["span_s"][s["name"]] = parts["span_s"].get(
            s["name"], 0.0) + s["end"] - s["start"]
    for part, event in EVENTS.items():
        parts[part] = union_s(
            [(e["start"], e["end"]) for e in events
             if e["event"] == event], t0, end)
    parts["cache_misses"] = sum(
        1 for e in events
        if e["event"] == CACHE_MISSES and t0 <= e["end"] <= end)
    return parts


def _record():
    try:
        from sparksched_tpu.obs import tracing
    except ImportError:
        return None
    return getattr(tracing, "RECORD", None)


def read(window: dict, part: str, span: str | None = None,
         may_lack: bool = False):
    scalars = window.get("scalars")
    t0 = getattr(sys.modules.get("__main__"), "T0", None)
    if not scalars or t0 is None:
        return None
    record = _record()
    if record is None:
        if may_lack:
            return None
        raise LookupError("the program keeps no record of host spans")
    parts = split(record.spans(), record.events(), t0,
                  int(scalars[0]["collection"]))
    if parts is None:
        return None
    return parts[part] if span is None else parts[part][span]
