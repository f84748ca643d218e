"""Named trace scopes so captured device traces are legible.

`annotate(name)` combines the two annotation mechanisms a jitted JAX
program needs for one Perfetto-readable label:

- `jax.named_scope(name)`: active at TRACE time — prefixes the HLO
  metadata of every op created inside the block, so the XLA device
  timeline groups the phase's kernels under the name;
- `jax.profiler.TraceAnnotation(name)`: active at RUN time on the host
  thread — marks the dispatch span in the host track (useful around
  un-jitted host phases like the trainer's collect/update calls).

Entering both is cheap and safe in either context (a TraceAnnotation
with no profiler running is a no-op; a named_scope outside tracing only
touches a thread-local name stack), so call sites don't have to care
which side of the jit boundary they are on.

The phases the codebase labels. A decision row of the single-eval
collectors (`trainers/rollout.py`) is covered whole: `collect/observe`
(the vmapped `observe`), `decima/features`, `decima/gnn` (the net, with
`decima/gnn/levels`, `decima/gnn/stage_head` and `decima/gnn/exec_head`
inside it), `decima/sample`, `env/micro_step/decide` and
`env/micro_step/drain` (the engine's two functions of that row, so the
serve programs carry them too), `env/micro_step/reset` (streaming
only: `drain_to_decision(auto_reset=True)` re-seeds a lane whose
episode ended once, after its loop and beside `drain`, not inside it,
under one predicate for the batch: the reset program and the select of
the state against it), `collect/health`, `collect/freeze`,
`collect/scatter`. Before the scan, once a collection: `collect/reset`
(`Trainer._collect`: the reset program of every lane; in streaming mode
only where the lanes do not persist yet). Elsewhere: `env/micro_step`
(the mode switch and tail of `flat_loop.micro_step`; with `auto_reset`
the tail of the loops whose unit is the micro-step holds
`env/micro_step/reset` in every step),
`train/ppo_update`, `serve/decide`, `serve/decide_batch`,
`serve/dispatch`, `serve/flush`. A decision row of the sweep loop
(`sparksched_tpu/sweep.py`) runs under `collect/observe`, `sweep/policy`
(the scheduler's evaluation over the batch; a Decima scheduler's own
scopes sit inside it), `env/micro_step/decide`, `env/micro_step/drain`,
`env/micro_step/reset` (in every row in which a lane of the drain's
block ended its episode), `collect/health` and `sweep/record` (the
row's store, the episode's result and the row counters).

A nested phase is ONE scope whose name holds its parent's
(`annotate("env/micro_step/drain")`, not two nested `annotate`s): a
device operation's `op_name` is the path of scopes and transforms it
was traced under, a scope entered inside `jax.vmap` reads
`vmap(env/micro_step)/drain` there, and flax puts its module names
between a caller's scope and a module's own. The trace reducer matches
a scope as a substring of `op_name`, so only a whole name is found under
its own name and under its parent's. For the same reason a new
top-level name must not contain an existing one.

Exception safety: a raise inside the annotated block (or inside one of
the two underlying exits) must still pop the named-scope stack — a
leaked scope prefixes every LATER trace's labels with a dead phase
name, corrupting the whole capture, not just the failing region. Both
context managers live on a `contextlib.ExitStack`, whose `__exit__`
guarantees LIFO unwinding even when an inner exit raises;
`tests/test_obs.py::test_annotate_exception_safe` pins it.

Host spans (PR 44). `span(name)` times a block of HOST work into the
process's one `RECORD` and marks it on the host track of a running
profiler; it enters no `named_scope`, so its name reaches no `op_name`
and no compiled program changes. The spans the codebase opens:
`setup/mesh` (`make_trainer`: `mesh_from_config`), `setup/trainer_init`
(`Trainer.__init__`) with `setup/workload_bank` (`make_workload_bank`)
and `setup/scheduler_init` (`make_scheduler`: the flax init and what it
compiles) inside it, `setup/init_state` (`Trainer.init_state`),
`collect/call` and `train/update_call` (every call of the trainer's
compiled collector and update: trace, lower and compile or load on a
first call, dispatch alone afterwards; the caller blocks on the result
itself), `sweep/chunk_call` (the same around every call of the sweep's
compiled chunk) with `setup/sweep_init` (`sweep.init`: the reset
program of every lane) and, in `sweep.from_config`, the trainer's
`setup/workload_bank` and `setup/scheduler_init`, and whatever `RunLog.span` and `trainers.Profiler` are given
(`iter <n> collect`, `iter <n> update`). jax's own compile events land
in the same record, each under the span it fell in (`JAX_TIMED`,
`JAX_DURATIONS`, `JAX_COUNTED` below). A host span's name must not
contain a device scope's name, nor a device scope's name a host span's.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import os
import threading
import time
import weakref


class annotate:
    """Context manager: `with annotate("decima/gnn"): ...`"""

    def __init__(self, name: str) -> None:
        self.name = name
        self._stack: contextlib.ExitStack | None = None

    def __enter__(self) -> "annotate":
        import jax

        stack = contextlib.ExitStack()
        stack.enter_context(jax.named_scope(self.name))
        try:
            stack.enter_context(jax.profiler.TraceAnnotation(self.name))
        except Exception:
            pass  # profiler backend unavailable: scope only
        self._stack = stack
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        stack, self._stack = self._stack, None
        if stack is not None:
            return stack.__exit__(exc_type, exc_val, exc_tb)
        return False


# ---------------------------------------------------------------------------
# host spans and jax's compile events: one record a process (PR 44)
# ---------------------------------------------------------------------------

SETUP_PREFIX = "setup/"  # spans under it are kept for the process's life

# what jax reports of a jitted function's way to the device, by kind.
# Timed: a start and an end (`record_event_time_span`); trace events
# NEST (a jitted helper traced inside another trace reports its own),
# so a phase's tracing time is the union of the intervals, not their
# sum. `backend_compile_duration` wraps the persistent cache's read: on
# a hit it IS the load.
JAX_TIMED = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
# a duration alone, stamped on arrival: what a cache hit saved, for the
# runlog's `jit_compile` record (the read itself is the hit's
# `backend_compile_duration`, and a run with no `cache_misses` hit
# every time, so the cache's read time and its count of hits are not
# kept a second time)
JAX_DURATIONS = ("/jax/compilation_cache/compile_time_saved_sec",)
# an occurrence, stamped on arrival: it comes with the entry's write,
# so only for a program the cache keeps
JAX_COUNTED = ("/jax/compilation_cache/cache_misses",)


class SpanRecord:
    """Ended host spans and jax's compile events, bounded: the spans
    under `setup/` are held apart, so no number of collections evicts
    one (the newest `setup_cap` of them: a hundred trainers' worth),
    every other span and every event lives in a ring. Times are
    `time.perf_counter()`; a span's `wall` is `time.time()` at its
    start. Sinks are held weakly: a span sink's `span_ended(rec)` gets
    every announced span as it ends, an event sink's `jax_event(rec)`
    every event."""

    def __init__(self, ring: int = 512, setup_cap: int = 512,
                 events: int = 1 << 16) -> None:
        self._lock = threading.Lock()
        self._setup: collections.deque = collections.deque(maxlen=setup_cap)
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self._events: collections.deque = collections.deque(maxlen=events)
        self.span_sinks: weakref.WeakSet = weakref.WeakSet()
        self.event_sinks: weakref.WeakSet = weakref.WeakSet()

    def add_span(self, rec: dict, announce: bool = True) -> None:
        with self._lock:
            held = (self._setup if rec["name"].startswith(SETUP_PREFIX)
                    else self._ring)
            held.append(rec)
            sinks = list(self.span_sinks) if announce else ()
        for sink in sinks:
            try:
                sink.span_ended(rec)
            except Exception:
                pass  # a closed or broken sink must not break the caller

    def add_event(self, rec: dict) -> None:
        with self._lock:
            self._events.append(rec)
            sinks = list(self.event_sinks)
        for sink in sinks:
            try:
                sink.jax_event(rec)
            except Exception:
                pass  # nor a compilation

    def spans(self) -> list[dict]:
        """Every span held, in the order they started."""
        with self._lock:
            held = list(self._setup) + list(self._ring)
        return sorted(held, key=lambda r: r["ordinal"])

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)


RECORD = SpanRecord()
_ORDINALS = itertools.count()
_OPEN = threading.local()  # .stack: this thread's open spans
_LISTENING = False
_LISTEN_LOCK = threading.Lock()


def _open_spans() -> list:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


def open_span() -> "span | None":
    """The innermost span this thread has open."""
    stack = _open_spans()
    return stack[-1] if stack else None


def mark() -> int:
    """An ordinal that is no span's: above that of every span opened so
    far and below that of every later one."""
    return next(_ORDINALS)


def _note_event(event: str, secs: float, fields: dict) -> None:
    end = time.perf_counter()
    inside = open_span()
    RECORD.add_event({
        "event": event, "fun_name": fields.get("fun_name"),
        # `compile_time_saved_sec` is a saving, not an interval, and
        # may be negative: `secs` holds it, `start` never passes `end`
        "start": end - max(secs, 0.0), "end": end, "secs": secs,
        "span": inside.ordinal if inside else None,
    })


def listen_to_jax() -> None:
    """Registers the record's listeners with `jax.monitoring`, once a
    process (they cannot be told apart later, so never twice). A timed
    event arrives as it ends, on the thread that did the work: its end
    is now on this module's clock and its start its length earlier, so
    spans and events share one clock though jax stamps `time.time()`."""
    global _LISTENING
    if _LISTENING:
        return
    with _LISTEN_LOCK:
        if _LISTENING:
            return
        from jax import monitoring

        def on_time_span(event, start_time, end_time, **fields):
            if event in JAX_TIMED:
                _note_event(event, end_time - start_time, fields)

        def on_duration(event, duration, **fields):
            if event in JAX_DURATIONS:
                _note_event(event, float(duration), fields)

        def on_event(event, **fields):
            if event in JAX_COUNTED:
                _note_event(event, 0.0, fields)

        monitoring.register_event_time_span_listener(on_time_span)
        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        _LISTENING = True


class span(contextlib.ContextDecorator):
    """Times a block of host work into `RECORD`: `with span("setup/x"):`
    or `@span("setup/x")` on a function. Keeps `name`, `start` and `end`
    (`perf_counter`), `wall` (`time.time()` at the start), `ordinal`
    (process-wide, in starting order) and `parent` (the ordinal of the
    span this thread had open, or None), and enters a
    `jax.profiler.TraceAnnotation` of the same name, so a running
    profiler shows the span on the host track of the device's own trace.
    No `named_scope`: see the module docstring. A raise inside still
    ends and records the span (`error` names it). `announce=False`
    keeps the span from the record's span sinks: for an owner that
    reports it itself (`RunLog.span`, a `Profiler` with a sink)."""

    def __init__(self, name: str, announce: bool = True) -> None:
        self.name = name
        self.announce = announce
        self.elapsed = 0.0

    def _recreate_cm(self) -> "span":
        return span(self.name, self.announce)  # one object a call

    def __enter__(self) -> "span":
        import jax

        listen_to_jax()
        inside = open_span()
        self.parent = inside.ordinal if inside else None
        self.ordinal = next(_ORDINALS)
        self._annotation = None
        try:
            self._annotation = jax.profiler.TraceAnnotation(self.name)
            self._annotation.__enter__()
        except Exception:
            self._annotation = None  # profiler backend unavailable
        _open_spans().append(self)
        self.wall = time.time()
        self.start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> bool:
        self.end = time.perf_counter()
        self.elapsed = self.end - self.start
        stack = _open_spans()
        if self in stack:  # a span left open inside this one goes too
            del stack[stack.index(self):]
        rec = {"name": self.name, "start": self.start, "end": self.end,
               "wall": self.wall, "ordinal": self.ordinal,
               "parent": self.parent}
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        try:
            if self._annotation is not None:
                self._annotation.__exit__(exc_type, exc_val, exc_tb)
        finally:
            RECORD.add_span(rec, self.announce)
        return False


class spanned:
    """A callable under a host span: `spanned("collect/call", jitted)`
    opens the span around every call and is the wrapped object in every
    other respect (`.lower`, `.trace`, `.eval_shape`, `.clear_cache`).
    Around a `jax.jit` object the span is the HOST's part of the call:
    trace, lower and compile or load when the arguments are new to it,
    dispatch alone otherwise; the device's run is the caller's to wait
    for."""

    def __init__(self, name: str, fn) -> None:
        self._name = name
        self._fn = fn

    def __call__(self, *args, **kwargs):
        with span(self._name):
            return self._fn(*args, **kwargs)

    def __getattr__(self, attr: str):
        return getattr(self._fn, attr)


# ---------------------------------------------------------------------------
# per-request span traces (ISSUE 11)
#
# The serving path's Dapper-style walk: a trace id minted at `Ticket`
# creation, one perf_counter stamp per phase as the request moves
# submit -> batch_admit -> dispatch -> harvest -> device_compute ->
# scatter_back -> reply. `harvest` (ISSUE 15) is the instant the host
# STARTS materializing the call — immediately after dispatch on the
# synchronous front, one full in-flight residency later under the
# pipelined front (dispatch -> harvest is the pipeline overlap the
# span exists to show).
# Host-side only — the compiled serve programs are untouched
# (the analysis registry pins them byte-identical), and the host
# phases bracket the device work: `dispatch` is the instant the
# compiled call is issued, `device_compute` when its outputs are ready
# (block_until_ready), `scatter_back` when the host has the concrete
# ServeResults (device_get + un-batching). The instrumented
# MicroBatcher additionally enters `annotate("serve/flush")` around
# the dispatch, so a Perfetto capture carries the same phase label the
# trace records use.
#
# Across the wire (ISSUE 16): the network client brackets the walk
# with `wire_submit` (the instant the request leaves the client) and
# `wire_reply` (the instant the decoded reply is in the client's
# hands). The server's spans ride back in the reply as offsets and
# are re-anchored so the server-side `submit` coincides with the
# client's `wire_submit` — by construction, `reply -> wire_reply`
# is then the request's total NETWORK + serialization overhead (both
# directions plus server-side parse), while `dispatch ->
# device_compute` stays the device share and the harvest spans the
# host share. One clock never spans two machines: each side stamps
# only its own perf_counter, and only OFFSETS cross the wire. The
# runlog `trace` record shape is unchanged — the wire spans are just
# two more keys in `spans_ms`.
# ---------------------------------------------------------------------------

SPAN_ORDER = (
    "wire_submit", "submit", "batch_admit", "dispatch", "harvest",
    "device_compute", "scatter_back", "reply", "wire_reply",
)

_TRACE_SEQ = itertools.count()


class RequestTrace:
    """One request's spans: `stamp(name)` records a perf_counter time;
    `offsets_ms()` converts to ms offsets from submit (the runlog
    `trace` record payload). Trace ids are process-unique and ordered
    (`t<pid>-<seq>`), deterministic given submission order."""

    __slots__ = ("trace_id", "spans")

    def __init__(self, trace_id: str | None = None) -> None:
        self.trace_id = (
            trace_id
            if trace_id is not None
            else f"t{os.getpid():x}-{next(_TRACE_SEQ):08d}"
        )
        self.spans: dict[str, float] = {}

    def stamp(self, name: str, t: float | None = None) -> None:
        self.spans[name] = time.perf_counter() if t is None else t

    def offsets_ms(self) -> dict[str, float]:
        base = self.spans.get("submit")
        if base is None:
            # a wire-side trace that never reached a server (429 /
            # transport error) still has its client bracket
            base = self.spans.get("wire_submit")
        if base is None:
            return {}
        return {
            name: (self.spans[name] - base) * 1e3
            for name in SPAN_ORDER
            if name in self.spans
        }
