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
`serve/dispatch`, `serve/flush`.

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
"""

from __future__ import annotations

import contextlib
import itertools
import os
import time


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
