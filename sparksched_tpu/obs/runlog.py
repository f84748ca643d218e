"""Structured JSONL run log.

One file per run under `artifacts/`, one JSON object per line — the
machine-readable replacement for the trainer's ad-hoc stdout lines and
the print-based profiler reports. Every record carries `ev` (the event
kind) and `t` (unix seconds); the kinds the trainer/bench write:

- `run_start` / `run_end`: run metadata (config summary, totals)
- `span`: a timed host-side phase (`name`, `secs`). The trainer's
  runlog holds the process's host spans (`obs/tracing.py` lists them):
  after `run_start` the start-up split (`setup/mesh`,
  `setup/trainer_init` with `setup/workload_bank` and
  `setup/scheduler_init`, `setup/init_state`), then every iteration a
  `collect/call` and a `train/update_call` (the host's part of the
  compiled call: seconds of it after the first iteration are a
  re-trace) inside `iter <n> collect` and `iter <n> update` (the call
  and the device's run); those from the record carry `ordinal`,
  `parent` and `started` (unix seconds)
- `scalars`: per-iteration training stats (the TensorBoard mirror —
  identical keys/values to what `add_scalar` receives)
- `telemetry`: an engine-telemetry summary (`obs.telemetry.summarize`)
- `memory`: a device-memory sample (`obs.memory.device_memory_stats`
  fields — `bytes_in_use` / `peak_bytes_in_use` — plus the optional
  `iteration`/`phase` the sample brackets)
- `latency`: a decision-latency sample from the serving path
  (ISSUE 10) — the measured percentile block (`p50_ms` / `p90_ms` /
  `p99_ms` / `mean_ms`), the `batch` width and `reps` behind it, and
  cold-start fields; `sparksched_tpu/serve/` sessions additionally
  write per-iteration `serve_*` scalars through the standard
  `scalars` record (TensorBoard-mirrored like the trainer's)
- `trace`: one served request's Dapper-style span walk (ISSUE 11) —
  the `trace_id` minted at `Ticket` creation plus per-phase offsets
  in ms from submit (`submit` -> `batch_admit` -> `dispatch` ->
  `device_compute` -> `scatter_back` -> `reply`) and `total_ms`;
  written by the instrumented `MicroBatcher`, off by default
- `metrics`: a `MetricsRegistry` snapshot (obs/metrics.py) — the
  JSONL half of the exporter pair (counters / gauges / streaming-
  histogram summaries nested under `snapshot`); the Prometheus text
  form is `MetricsRegistry.to_prometheus`
- `health`: a tripped in-JIT health sentinel (ISSUE 9) — the raw i32
  violation bitmask (`mask`), its decoded `bits` (env/health.py bit
  table), the `iteration`/`attempt` it quarantines, and the recovery
  `action` taken (rollback_retry | quarantine | gave_up)
- `recovery`: a recovery-policy outcome — rollback+retry with its
  backoff, a checkpoint fallback past a corrupt generation, or a
  gave-up marker; `chaos` records mark deliberate fault injections
  (sparksched_tpu/chaos.py) so drills are self-describing
- `params_swap`: a hot parameter swap into live serving (ISSUE 14) —
  the new `version`, the `prev_version` it replaced, the `action`
  (swap | rollback) and an optional origin/reason; written by
  `SessionStore.set_params`/`rollback_params` so every served
  decision's staleness stamp (`params_version` on `trace` records)
  can be aligned with the swap history
- `jit_compile`: a JIT (re)compilation event of `JIT_MIN_SECS` or more,
  from the `jax.monitoring` listeners of `obs/tracing.py`'s record: the
  phase (`event`: trace, lower, backend compile or cache load, compile
  time saved), `secs`, `fun_name`, WHICH function it was, and `span`,
  the `ordinal` of the `span` record it fell in (which `collect/call`
  re-traced; null outside any)
- `fleet`: a fleet-collector scoreboard snapshot (ISSUE 17) — per-
  replica windowed rps/p99/occupancy/page-churn/quarantine-rate/
  params-version(+lag) rows and the fleet-aggregate window, written
  periodically by `obs.fleet.FleetCollector`
- `alert`: an SLO burn-rate breach (ISSUE 17) — the spec name, both
  window burn rates, the rule that fired, and the action taken
  (`none` | `rollback`); written by `obs.slo.SLOMonitor`

Crash-safety: every record is flushed at write time, and open runlogs
are closed (a final `run_end` with a `teardown` reason) from an
`atexit` hook and — when the process had no handler of its own — a
chained SIGTERM handler, so a watcher-timeout-killed run keeps its
partial telemetry instead of losing the tail.

Rotation (ISSUE 11): `max_bytes` caps the active file — a write that
pushes past it renames the file to `<path>.<n>` (numbered suffix,
monotone across process restarts) and reopens `<path>` fresh with a
`rotate` continuation record, so a million-request open-loop run can
never grow one unbounded JSONL. Rotated segments are complete (every
record was flushed when written) and the crash-safety guarantees are
unchanged: teardown stamps `run_end` into the ACTIVE file and never
rotates (the signal path must not rename/reopen mid-kill).

Readers: `PERF_ROUNDS.md` "Reading a run" documents the schema; a runlog is
greppable (`grep '"ev": "telemetry"' run.jsonl | tail -1`) and loads
with one `json.loads` per line.
"""

from __future__ import annotations

import atexit
import json
import os
import os.path as osp
import signal
import sys
import threading
import time
import weakref
from typing import Any

from . import tracing

# sanctioned console sink: the lint tier forbids bare `print(` inside
# sparksched_tpu/ outside renderer.py, so host-loop progress lines go
# through here (stdout, line-flushed — same observable behavior as the
# print(..., flush=True) calls this replaces)


def emit(msg: str) -> None:
    sys.stdout.write(msg + "\n")
    sys.stdout.flush()


_CREATE_COUNTER = 0


def _json_safe(v: Any) -> Any:
    """Best-effort scalarization: numpy/jax scalars -> python numbers,
    everything non-serializable -> str."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _json_safe(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_json_safe(x) for x in v]
    try:
        import numpy as np

        if isinstance(v, np.ndarray) and v.ndim == 0:
            v = v.item()
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
    except Exception:
        pass
    if hasattr(v, "item"):
        try:
            return _json_safe(v.item())
        except Exception:
            pass
    return str(v)


class RunLog:
    """Append-only JSONL writer (thread-safe; the JIT hooks fire from
    whatever thread compiles)."""

    def __init__(self, path: str, echo: bool = False,
                 max_bytes: int | None = None) -> None:
        os.makedirs(osp.dirname(osp.abspath(path)), exist_ok=True)
        self.path = path
        self.echo = echo
        self.max_bytes = int(max_bytes) if max_bytes else None
        self._lock = threading.Lock()
        self._fp = open(path, "a")
        self._closed = False
        # resume rotation numbering past any suffixes already on disk
        # (RunLog appends; clobbering an earlier run's `.1` would break
        # the "rotated segments are complete" promise)
        self._rotations = 0
        if self.max_bytes:
            import glob as _glob

            # escape the path itself: a user-supplied runlog path with
            # glob metachars must not silently restart numbering at 0
            # (os.replace would then clobber an earlier run's segments)
            for p in _glob.glob(_glob.escape(path) + ".*"):
                tail = p[len(path) + 1:]
                if tail.isdigit():
                    self._rotations = max(self._rotations, int(tail))
        _OPEN_RUNLOGS.add(self)
        _install_teardown_hooks()

    @classmethod
    def create(cls, artifacts_dir: str, name: str | None = None,
               echo: bool = False,
               max_bytes: int | None = None) -> "RunLog":
        """Open `artifacts_dir/runlog/<name>.jsonl`. The default name
        carries pid + a process-local counter on top of the timestamp
        so two runs started within the same second (back-to-back tests,
        quick A/B scripts) never interleave into one file — RunLog
        appends, and the schema promises one run per file."""
        if name is None:
            global _CREATE_COUNTER
            _CREATE_COUNTER += 1
            name = (
                f"run-{int(time.time())}-{os.getpid()}-{_CREATE_COUNTER}"
            )
        return cls(
            osp.join(artifacts_dir, "runlog", f"{name}.jsonl"),
            echo=echo, max_bytes=max_bytes,
        )

    # -- record writers ----------------------------------------------------

    def write(self, ev: str, **fields: Any) -> None:
        # double-checked fast path: a racy True is re-verified under
        # the lock below; a racy False only skips a record on a log
        # that is closing anyway
        if self._closed:  # analysis: allow(concurrency-unlocked-shared)
            return
        rec = {"ev": ev, "t": round(time.time(), 3)}
        rec.update({k: _json_safe(v) for k, v in fields.items()})
        line = json.dumps(rec)
        with self._lock:
            if self._closed:
                return
            self._fp.write(line + "\n")
            self._fp.flush()
            # run_end must stay the active file's last record (the
            # schema promise readers and the crash-safety tests pin),
            # so the closing write never triggers a rotation
            if (self.max_bytes and ev != "run_end"
                    and self._fp.tell() >= self.max_bytes):
                self._rotate_locked()
        if self.echo:
            emit(line)

    def _rotate_locked(self) -> None:
        """Size-cap rotation (caller holds the lock): rename the full
        active file to `<path>.<n>` and reopen `<path>` with a
        `rotate` continuation record. Best-effort — a failed rename
        (read-only fs mid-run) keeps appending to the active file
        rather than losing records."""
        try:
            self._fp.close()
            self._rotations += 1
            os.replace(self.path, f"{self.path}.{self._rotations}")
            self._fp = open(self.path, "a")
            cont = {"ev": "rotate", "t": round(time.time(), 3),
                    "segment": self._rotations,
                    "prev": f"{self.path}.{self._rotations}"}
            self._fp.write(json.dumps(cont) + "\n")
            self._fp.flush()
        except OSError:
            self._fp = open(self.path, "a")

    def span(self, name: str, **fields: Any) -> "_Span":
        """Context manager timing a block; writes one `span` record with
        `secs` on exit (exception-safe — the record is written either
        way, with `error` set when the block raised)."""
        return _Span(self, name, fields)

    def span_event(self, name: str, secs: float, **fields: Any) -> None:
        """A span measured elsewhere (e.g. by `trainers.Profiler`)."""
        self.write("span", name=name, secs=round(float(secs), 4), **fields)

    def scalars(self, iteration: int, stats: dict[str, Any]) -> None:
        self.write("scalars", iteration=int(iteration), **stats)

    def telemetry(self, summary: dict[str, Any],
                  iteration: int | None = None, **fields: Any) -> None:
        if iteration is not None:
            fields["iteration"] = int(iteration)
        self.write("telemetry", summary=summary, **fields)

    def health(self, mask: int, iteration: int | None = None,
               **fields: Any) -> None:
        """A tripped health sentinel (ISSUE 9): the raw violation
        bitmask plus its decoded bit names (env/health.py bit table),
        so `grep '"ev": "health"'` reads without the table. The
        trainer adds `attempt` and the recovery `action` taken;
        recovery outcomes themselves land as `recovery` records."""
        from ..env.health import describe_mask  # host-side, no cycle

        if iteration is not None:
            fields["iteration"] = int(iteration)
        self.write(
            "health", mask=int(mask), bits=describe_mask(mask), **fields
        )

    def latency(self, stats: dict[str, Any],
                iteration: int | None = None, phase: str | None = None,
                **fields: Any) -> None:
        """A decision-latency sample (ISSUE 10 serving path): the
        percentile block the latency bench measures (`p50_ms` /
        `p90_ms` / `p99_ms` / `mean_ms`, plus `batch`, `reps`,
        cold-start fields). Keys land top-level so runlogs stay
        greppable (`grep '"ev": "latency"'`), like `memory` records."""
        if iteration is not None:
            fields["iteration"] = int(iteration)
        if phase is not None:
            fields["phase"] = phase
        self.write("latency", **(dict(stats or {}) | fields))

    def trace(self, trace_id: str, spans_ms: dict[str, float],
              **fields: Any) -> None:
        """One served request's span walk (ISSUE 11): `spans_ms` maps
        phase name -> offset in ms from submit (obs/tracing.py:
        `RequestTrace.offsets_ms`); `total_ms` is stamped from the
        `reply` offset so a grep can read tail latency without
        arithmetic."""
        total = spans_ms.get("reply")
        self.write(
            "trace", trace_id=trace_id,
            spans={k: round(float(v), 4) for k, v in spans_ms.items()},
            total_ms=None if total is None else round(float(total), 4),
            **fields,
        )

    def params_swap(self, version: int, prev_version: int,
                    action: str = "swap",
                    reason: str | None = None,
                    **fields: Any) -> None:
        """One hot parameter swap into live serving (ISSUE 14):
        versioned so staleness stamps on `trace` records and the
        trajectory buffer resolve against the swap history. `action`
        is `swap` (a learner publish) or `rollback` (the
        quarantine-style revert to the last-good version)."""
        if reason is not None:
            fields["reason"] = reason
        self.write(
            "params_swap", version=int(version),
            prev_version=int(prev_version), action=action, **fields,
        )

    def metrics(self, snapshot: dict[str, Any],
                iteration: int | None = None, **fields: Any) -> None:
        """A `MetricsRegistry.snapshot()` (obs/metrics.py) — the JSONL
        exporter: counters/gauges/histogram summaries nested under
        `snapshot` (one record per export, like `telemetry`)."""
        if iteration is not None:
            fields["iteration"] = int(iteration)
        self.write("metrics", snapshot=snapshot, **fields)

    def memory(self, stats: dict[str, Any],
               iteration: int | None = None, phase: str | None = None,
               **fields: Any) -> None:
        """A device-memory sample (`obs.memory.device_memory_stats`
        output); the allocator's keys land top-level so runlogs stay
        greppable (`grep '"ev": "memory"'`)."""
        if iteration is not None:
            fields["iteration"] = int(iteration)
        if phase is not None:
            fields["phase"] = phase
        self.write("memory", **(dict(stats or {}) | fields))

    def fleet(self, **status: Any) -> None:
        """One fleet-collector scoreboard snapshot (ISSUE 17): the
        per-replica rows (rps/p99/occupancy/page churn/quarantine
        rate/params version+lag) plus the fleet-aggregate window, as
        `obs.fleet.FleetCollector.scrape` computed them. Periodic —
        one record every `log_every` scrapes."""
        self.write("fleet", **status)

    def alert(self, slo: str, **fields: Any) -> None:
        """An SLO burn-rate alert (ISSUE 17): the spec that breached
        (`slo`), both window burn rates (`burn_long`/`burn_short`),
        the rule's windows/factor, and the `action` taken (`none` or
        `rollback` via the ParamBus/store facade). Written by
        `obs.slo.SLOMonitor` at fire time, rate-limited by its
        per-spec cooldown."""
        self.write("alert", slo=slo, **fields)

    def tail_exemplar(self, trace_id: str | None, wall_ms: float,
                      segments: dict[str, float],
                      **fields: Any) -> None:
        """One of the slowest-N requests of an attribution window
        (ISSUE 20): the critical-path segment decomposition of a
        concrete tail request (`segments` sums to `wall_ms` exactly —
        obs/critpath.py `decompose`), plus its tenant/replica/error
        and its `rank` within the window (0 = slowest). Emitted by
        `CritPathAnalyzer.flush_window`, so a p99 incident ships
        traces, not just a number."""
        self.write(
            "tail_exemplar", trace_id=trace_id,
            wall_ms=round(float(wall_ms), 4),
            segments={k: round(float(v), 4)
                      for k, v in segments.items()},
            **fields,
        )

    def hostprof(self, **tables: Any) -> None:
        """One role-attributed host-profile dump (ISSUE 20): the
        per-role self-time tables from `obs.hostprof.HostProfiler`
        (samples, share, estimated self-ms, top innermost sites per
        role). Written once at profiler `stop()`."""
        self.write("hostprof", **tables)

    # -- JIT recompile hooks ----------------------------------------------

    def install_jit_hooks(self) -> None:
        """Record JIT (re)compilations into this runlog: a `jit_compile`
        record for each of jax's compile events of `JIT_MIN_SECS` or
        longer, with the phase (`event`), `secs`, the function
        (`fun_name`) and the host span it fell in (`span`). Multiple
        runlogs each receive the events while open."""
        tracing.listen_to_jax()
        tracing.RECORD.event_sinks.add(self)

    def jax_event(self, rec: dict) -> None:
        """The record's event sink (see `install_jit_hooks`)."""
        if "compile" in rec["event"] and rec["secs"] >= JIT_MIN_SECS:
            self.write("jit_compile", event=rec["event"],
                       secs=round(rec["secs"], 4),
                       fun_name=rec["fun_name"], span=rec["span"])

    def follow_spans(self, since: int = 0) -> None:
        """Write the host spans the process's record holds from ordinal
        `since` on (`obs/tracing.py`; a trainer passes where its own
        set-up began, or where its last run ended, so another trainer's
        and an earlier run's spans stay out) as `span` records, and
        every announced span from now on as it ends: the start-up split
        and one `collect/call` an iteration."""
        for rec in tracing.RECORD.spans():
            if rec["ordinal"] >= since:
                self.span_ended(rec)
        tracing.RECORD.span_sinks.add(self)

    def span_ended(self, rec: dict) -> None:
        """The record's span sink (see `follow_spans`)."""
        fields = {k: rec[k] for k in ("ordinal", "parent", "error")
                  if rec.get(k) is not None}
        self.span_event(rec["name"], rec["end"] - rec["start"],
                        started=round(rec["wall"], 3), **fields)

    def close(self, **fields: Any) -> None:
        # double-checked fast path (idempotent close): the
        # authoritative check is write()'s locked re-test
        if self._closed:  # analysis: allow(concurrency-unlocked-shared)
            return
        self.write("run_end", **fields)
        with self._lock:
            self._closed = True
            self._fp.close()
        self._forget()

    def _forget(self) -> None:
        """A closed runlog receives nothing more."""
        tracing.RECORD.event_sinks.discard(self)
        tracing.RECORD.span_sinks.discard(self)
        _OPEN_RUNLOGS.discard(self)

    def _teardown(self, reason: str) -> None:
        """Signal-context close: never blocks on the writer lock. A
        SIGTERM handler runs on the main thread at the next bytecode
        boundary — possibly INSIDE a write() still holding the
        (non-reentrant) lock, mid-line; blocking would deadlock the
        process, and writing anyway would interleave into a corrupt
        line. If the lock is free, stamp run_end and close; otherwise
        leave the file exactly as the per-write flushes left it (every
        completed line already on disk, still parseable)."""
        if self._closed or not self._lock.acquire(blocking=False):
            return
        try:
            if self._closed:
                return
            try:
                rec = {"ev": "run_end", "t": round(time.time(), 3),
                       "teardown": reason}
                self._fp.write(json.dumps(rec) + "\n")
                self._fp.flush()
            finally:
                self._closed = True
                self._fp.close()
        finally:
            self._lock.release()
        self._forget()

    def __enter__(self) -> "RunLog":
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self.close()


class _Span:
    """`RunLog.span`: timed by `tracing.span` (so it is in the process's
    record and on a running profiler's host track), written here."""

    def __init__(self, log: RunLog, name: str, fields: dict) -> None:
        self._log = log
        self._fields = fields
        self._span = tracing.span(name, announce=False)
        self.elapsed = 0.0

    def __enter__(self) -> "_Span":
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb) -> None:
        self._span.__exit__(exc_type, exc_val, exc_tb)
        self.elapsed = self._span.elapsed
        fields = dict(self._fields)
        if exc_type is not None:
            fields["error"] = exc_type.__name__
        self._log.span_event(self._span.name, self.elapsed, **fields)


# ---------------------------------------------------------------------------
# crash-safe teardown
#
# Watcher-killed runs (`timeout -k`, chip-window handovers) must keep
# their partial telemetry. Records are already flushed per write, so
# even SIGKILL loses at most nothing; the hooks below additionally
# stamp a final `run_end` (with a `teardown` reason) on the exits a
# process can still observe: interpreter shutdown (`atexit` — covers
# normal exit, sys.exit and uncaught exceptions) and SIGTERM. The
# SIGTERM handler is installed only when the process has none of its
# own (SIG_DFL), runs only in the main thread, and re-raises the
# default disposition afterwards so exit-status semantics (rc 143 /
# `timeout` accounting) are unchanged.
# ---------------------------------------------------------------------------

_OPEN_RUNLOGS: "weakref.WeakSet[RunLog]" = weakref.WeakSet()
_ATEXIT_INSTALLED = False
_SIGTERM_INSTALLED = False


def _close_open_runlogs(reason: str, from_signal: bool = False) -> None:
    for rl in list(_OPEN_RUNLOGS):
        try:
            if from_signal:
                rl._teardown(reason)  # must not block on the lock
            else:
                rl.close(teardown=reason)
        except Exception:
            pass  # teardown must never mask the original exit


def _install_teardown_hooks() -> None:
    global _ATEXIT_INSTALLED, _SIGTERM_INSTALLED
    if not _ATEXIT_INSTALLED:
        _ATEXIT_INSTALLED = True
        atexit.register(_close_open_runlogs, "atexit")
    if _SIGTERM_INSTALLED:
        return
    if threading.current_thread() is not threading.main_thread():
        # signal.signal is main-thread-only; leave the flag unset so a
        # later RunLog created on the main thread still installs it
        return
    try:
        prev = signal.getsignal(signal.SIGTERM)
    except (ValueError, OSError):
        return
    if prev is not signal.SIG_DFL:
        # the app owns SIGTERM (or a non-Python handler is active);
        # atexit still covers clean exits — stop probing
        _SIGTERM_INSTALLED = True
        return

    def _on_sigterm(signum, frame):
        # restore the default disposition FIRST: if teardown ever
        # blocks, a second SIGTERM must still kill the process
        signal.signal(signum, signal.SIG_DFL)
        _close_open_runlogs("sigterm", from_signal=True)
        os.kill(os.getpid(), signum)

    try:
        signal.signal(signal.SIGTERM, _on_sigterm)
        _SIGTERM_INSTALLED = True
    except (ValueError, OSError):
        pass


# ---------------------------------------------------------------------------
# JIT compile records
#
# The process has ONE pair of `jax.monitoring` listeners, those of
# `obs/tracing.py`'s record (jax's listeners cannot be told apart once
# registered); a runlog that asked for them (`install_jit_hooks`) is one
# of the record's event sinks while it is open (held weakly: a
# garbage-collected runlog stops receiving without explicit teardown).
# ---------------------------------------------------------------------------

# compiles shorter than this are not WRITTEN (the record keeps them):
# the hundreds of trivial broadcast/convert compiles at process start
# would bloat every runlog, while any recompile worth investigating (a
# shape leak, a cache miss mid-run) is orders of magnitude above it
JIT_MIN_SECS = float(os.environ.get("RUNLOG_JIT_MIN_SECS", "0.05"))
