"""Session-affinity scale-out: a router sharding sessions across N
serve-host replica processes (ISSUE 16, ROADMAP item 2's second step).

One `SessionStore` is single-threaded by contract (the donation
discipline: exactly one live reference to the device store), so
horizontal scale means PROCESSES, not threads — the reference repo's
mp.Pipe rollout-worker shape applied to serving. Each replica process
owns a full serving stack: its own donated store, its own batching
front (the ISSUE-13/15 `ContinuousBatcher`, pipelined when the config
says so), its own pager, its own `MetricsRegistry`, and the shared
persistent AOT compilation cache (`config.enable_compilation_cache`)
so replica cold-start pays a cache LOAD, not a recompile.

Affinity is structural, not a routing table lookup: a session created
on replica `i` gets the global id `lsid * n + i`, so
`replica_of(gsid) == gsid % n` for the session's whole life — a sid
can never silently migrate, which is what makes the per-session device
state (the whole point of the store) safe. Replica DEATH therefore
fails the replica's sessions (`ReplicaDied`, a `SessionError`), it
never reroutes them: the device state died with the process, and a
fresh session on another replica is a different episode — the caller
(the loadgen's rotation, a real client's retry) must decide that, not
the router.

The router speaks BOTH duck-typed serving protocols at once, so every
existing consumer works unchanged across the process boundary:

- the batching-front protocol (`submit`/`poll`/`flush`/`pending`) for
  `run_open_loop` and the HTTP front's pump loop;
- the store-facade protocol (`create`/`close`/`set_params`/
  `rollback_params`/`stats`) for session lifecycle and for
  `online.ParamBus` — `pump()` lands a learner publish on EVERY
  replica (host-side pytree broadcast over the pipes, applied by each
  replica between compiled calls: zero recompiles, the params-as-
  runtime-argument contract), and probation reads the router's
  aggregated decision/quarantine counters.

Everything here is host bookkeeping: the compiled serve programs are
byte-identical to the in-process path (each replica builds them
through the same `store_from_config`), which is the zero-cost-off
story — fleet off means this module is never imported on the serving
path.
"""

from __future__ import annotations

import importlib
import multiprocessing as mp
import os
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from ..obs.runlog import emit
from ..ownership import assert_owner
from .session import (
    RemoteResult,
    SessionError,
    SessionQuarantined,
)


class ReplicaDied(SessionError):
    """The replica owning this session exited: the session's device
    state is gone, so the session is FAILED — never rerouted."""


# error type names a replica may send back; anything else degrades to
# RuntimeError (the generic store failure class)
_ERROR_TYPES: dict[str, type[Exception]] = {
    "SessionError": SessionError,
    "SessionQuarantined": SessionQuarantined,
    "KeyError": SessionError,
    "ValueError": ValueError,
    "RuntimeError": RuntimeError,
}


def _rebuild_error(etype: str, msg: str) -> Exception:
    return _ERROR_TYPES.get(etype, RuntimeError)(msg)


@dataclass(frozen=True)
class ReplicaSpec:
    """Everything a replica process needs to rebuild a full serving
    stack, picklable across an mp spawn boundary. `builder` names a
    module-level callable (`"module.path:function"`) returning
    `(env_params, bank, scheduler)` — replicas REBUILD rather than
    unpickle the stack, so a seeded builder gives every replica
    bit-identical initial params (`DecimaScheduler.init_params` is
    deterministic in its seed), which is what lets a later fleet-wide
    `set_params` assume one common aval structure."""

    builder: str
    builder_kwargs: dict[str, Any] = field(default_factory=dict)
    serve_cfg: dict[str, Any] = field(default_factory=dict)
    compile_cache: bool = True
    trace: bool = False


def resolve_builder(path: str):
    mod, sep, fn = path.partition(":")
    if not sep or not mod or not fn:
        raise ValueError(
            f"builder must be 'module.path:function', got {path!r}"
        )
    return getattr(importlib.import_module(mod), fn)


def _poison_session(store, sid: int) -> None:
    """Test hook (the chaos-tier pattern): corrupt one session's
    persistent per-job completion clock with NaN so its next decide
    trips the H_NONFINITE_TIME health sentinel — exactly the poison
    tests/test_serve.py injects in-process, made reachable across the
    process boundary so the quarantine-isolation invariant is testable
    against a real fleet."""
    import jax.numpy as jnp

    slot = int(store._slot_of[sid])
    if slot < 0:
        raise SessionError(f"session {sid} is not resident")
    g, l = divmod(slot, store.group_slots)
    st = store._stores[g]
    store._stores[g] = st.replace(
        env=st.env.replace(
            job_t_completed=st.env.job_t_completed.at[l].set(jnp.nan)
        )
    )


def _replica_main(conn, idx: int, spec: ReplicaSpec) -> None:
    """The replica process body: build the serving stack, handshake,
    then loop — drain pipe commands, pump the front, ship resolved
    tickets back. Runs until a `stop` command or pipe EOF."""
    try:
        from ..config import enable_compilation_cache
        from ..obs.metrics import MetricsRegistry
        from .session import front_from_config, store_from_config

        if spec.compile_cache:
            enable_compilation_cache()
        params, bank, scheduler = resolve_builder(spec.builder)(
            **spec.builder_kwargs
        )
        registry = MetricsRegistry()
        cfg = dict(spec.serve_cfg)
        # network + observability-plane keys ride the same `serve:`
        # block but belong to the router/server layer — strip before
        # the store sees them
        for k in ("host", "port", "replicas", "quota_sessions",
                  "quota_inflight", "collect", "collect_period_s",
                  "slo", "hostprof"):
            cfg.pop(k, None)
        store = store_from_config(
            cfg, params, bank, scheduler, metrics=registry,
            trace=spec.trace,
        )
        front = front_from_config(
            cfg, store, metrics=registry, trace=spec.trace,
        )
        # ISSUE 18: a ring-on replica parks drained trajectory chunks
        # (already host numpy, in stream order) in this outbox instead
        # of a local collector; the router's `ring_pump` fetches the
        # whole backlog in ONE `ring_chunks` round-trip — the batched
        # wire feed that replaces per-decision RPCs to the learner
        ring_out: list[tuple] = []
        if getattr(store, "_ring_on", False):
            store.ring_sink = ring_out.append
        conn.send(("ready", idx, {
            "capacity": store.capacity, "pid": os.getpid(),
            "front": front.front_name,
        }))
    except Exception as e:  # pragma: no cover - boot failure path
        try:
            conn.send(("boot_error", idx, type(e).__name__, str(e)))
        finally:
            conn.close()
        return

    def reply(rid: int, payload: Any) -> None:
        conn.send(("reply", rid, payload))

    def reply_err(rid: int, e: Exception) -> None:
        conn.send(("reply_err", rid, type(e).__name__, str(e)))

    tracked: dict[int, Any] = {}  # rid -> Ticket
    stop = False
    try:
        while True:
            timeout = 0.0 if (tracked or front.pending) else 0.05
            while conn.poll(timeout):
                msg = conn.recv()
                op, rid = msg[0], msg[1]
                try:
                    if op == "submit":
                        tracked[rid] = front.submit(msg[2])
                    elif op == "create":
                        reply(rid, {"sid": store.create(seed=msg[2])})
                    elif op == "close":
                        store.close(msg[2])
                        reply(rid, {"closed": msg[2]})
                    elif op == "set_params":
                        _, _, p, version, origin, reason, good = msg
                        reply(rid, {"version": store.set_params(
                            p, version=version, origin=origin,
                            reason=reason, mark_good=good,
                        )})
                    elif op == "rollback":
                        reply(rid, {
                            "version": store.rollback_params(msg[2])
                        })
                    elif op == "metrics":
                        reply(rid, (registry, dict(store.stats)))
                    elif op == "poison":
                        _poison_session(store, msg[2])
                        reply(rid, {"poisoned": msg[2]})
                    elif op == "ring_chunks":
                        # msg[2] (force) drains the device rings to
                        # the outbox first; otherwise ship whatever
                        # the normal triggers (cadence / harvest-idle
                        # / close / swap) already landed there
                        if msg[2]:
                            store.drain_ring(wait=True)
                        ents = list(ring_out)
                        ring_out.clear()
                        reply(rid, ents)
                    elif op == "stop":
                        stop = True
                        front.flush()
                        if getattr(store, "_ring_on", False):
                            store.drain_ring(wait=True)
                        reply(rid, {"stopped": idx})
                    else:
                        reply_err(rid, ValueError(
                            f"unknown replica op {op!r}"
                        ))
                except Exception as e:
                    reply_err(rid, e)
                timeout = 0.0
            front.poll()
            for rid in [r for r, t in tracked.items() if t.ready]:
                t = tracked.pop(rid)
                if t.error is not None:
                    conn.send(("result", rid, None,
                               (type(t.error).__name__, str(t.error))))
                else:
                    d = t.result.to_dict()
                    d["replica"] = idx
                    if t.trace is not None:
                        d["spans_ms"] = t.trace.offsets_ms()
                    conn.send(("result", rid, d, None))
            if stop and not tracked and not front.pending:
                return
    except (EOFError, BrokenPipeError, OSError):
        return  # router side went away: exit quietly
    finally:
        conn.close()


class _Replica:
    __slots__ = ("idx", "proc", "conn", "dead", "sessions", "info")

    def __init__(self, idx, proc, conn) -> None:
        self.idx = idx
        self.proc = proc
        self.conn = conn
        self.dead = False
        self.sessions = 0  # live sessions, the placement load signal
        self.info: dict[str, Any] = {}


class RouterTicket:
    """`Ticket`'s fleet twin: resolved by `Router.poll` when the
    owning replica ships the result (or dies)."""

    __slots__ = ("session_id", "submitted_at", "result", "error",
                 "trace")

    def __init__(self, session_id: int) -> None:
        self.session_id = session_id
        self.submitted_at = time.perf_counter()
        self.result: RemoteResult | None = None
        self.error: Exception | None = None
        self.trace = None

    @property
    def ready(self) -> bool:
        return self.result is not None or self.error is not None


class Router:
    """The session-affinity fleet front. See the module docstring for
    the protocol; construction SPAWNS `replicas` worker processes and
    blocks until every one handshakes ready (raising, and reaping the
    fleet, if any replica fails to boot).

    A replica uses the device its process is given. A chip belongs to
    one process, so on a chip host a fleet is either in-process
    one-device stores (no Router), or one replica process per chip
    with this parent off jax: nothing on the router side makes a jax
    device call (test-pinned), so the parent never claims a chip its
    replicas need."""

    def __init__(self, spec: ReplicaSpec, replicas: int = 2, *,
                 metrics=None, runlog=None, collector=None,
                 ring_period_s: float = 0.25,
                 start_timeout_s: float = 300.0) -> None:
        if replicas < 1:
            raise ValueError(f"need >= 1 replica, got {replicas}")
        self.spec = spec
        self.n = int(replicas)
        self.metrics = metrics
        self.runlog = runlog
        # ISSUE 18: the fleet-level trajectory sink (a
        # `TrajectoryBuffer`, duck-typed `ingest_chunk`/`on_close`).
        # Every replica's ring chunks land here with session ids
        # remapped to the global space, so one learner feeds off the
        # whole fleet without per-decision RPCs.
        self.collector = collector
        self.ring_period_s = float(ring_period_s)
        self._ring_next = 0.0
        self.front_name = f"router{self.n}"
        self.params_version = 0
        self.stats: dict[str, int] = {
            "serve_decisions": 0,
            "serve_quarantines": 0,
            "serve_capacity_rejections": 0,
            "serve_param_swaps": 0,
            "serve_param_rollbacks": 0,
            "serve_param_version": 0,
            "router_replica_deaths": 0,
            "router_sessions_failed": 0,
        }
        self._rid = 0
        self._tickets: dict[int, tuple[int, RouterTicket]] = {}
        self._replies: dict[int, tuple[Any, Exception | None]] = {}
        self._reply_owner: dict[int, int] = {}
        self._sid_map: dict[int, int] = {}  # gsid -> local sid
        self._failed: set[int] = set()
        self._stopped = False
        ctx = mp.get_context("spawn")
        self._replicas: list[_Replica] = []
        try:
            for i in range(self.n):
                parent, child = ctx.Pipe()
                proc = ctx.Process(
                    target=_replica_main, args=(child, i, spec),
                    daemon=True, name=f"serve-replica-{i}",
                )
                proc.start()
                child.close()
                self._replicas.append(_Replica(i, proc, parent))
            deadline = time.monotonic() + start_timeout_s
            for r in self._replicas:
                budget = deadline - time.monotonic()
                if budget <= 0 or not r.conn.poll(budget):
                    raise RuntimeError(
                        f"replica {r.idx} did not come up within "
                        f"{start_timeout_s:g}s"
                    )
                try:
                    msg = r.conn.recv()
                except (EOFError, OSError) as e:
                    raise RuntimeError(
                        f"replica {r.idx} died during boot "
                        f"(spawned processes re-import __main__: "
                        f"run from a real script/module)"
                    ) from e
                if msg[0] != "ready":
                    raise RuntimeError(
                        f"replica {r.idx} failed to boot: "
                        f"{msg[2] if len(msg) > 2 else msg!r}: "
                        f"{msg[3] if len(msg) > 3 else ''}"
                    )
                r.info = msg[2]
        except Exception:
            self.stop(timeout_s=5.0)
            raise
        emit(
            f"[router] fleet up: {self.n} replica(s), capacity "
            f"{sum(r.info.get('capacity', 0) for r in self._replicas)}"
            f" sessions, front {self._replicas[0].info.get('front')}"
        )

    # -- plumbing ----------------------------------------------------------

    def replica_of(self, gsid: int) -> int:
        return gsid % self.n

    def _next_rid(self) -> int:
        self._rid += 1
        return self._rid

    def _send(self, r: _Replica, msg: tuple) -> None:
        try:
            r.conn.send(msg)
        except (BrokenPipeError, OSError, EOFError):
            self._mark_dead(r)
            raise ReplicaDied(
                f"replica {r.idx} died (send failed)"
            ) from None

    def _mark_dead(self, r: _Replica) -> None:
        if r.dead:
            return
        r.dead = True
        self.stats["router_replica_deaths"] += 1
        try:
            r.conn.close()
        except OSError:
            pass
        # fail everything the replica owned: in-flight tickets error,
        # its sessions join the failed set — NOT rerouted (the device
        # state died with the process; see module docstring)
        failed_sids = [g for g in self._sid_map
                       if self.replica_of(g) == r.idx]
        for g in failed_sids:
            self._failed.add(g)
            del self._sid_map[g]
        self.stats["router_sessions_failed"] += len(failed_sids)
        for rid, (owner, tk) in list(self._tickets.items()):
            if owner == r.idx:
                tk.error = ReplicaDied(
                    f"replica {r.idx} died with the request in flight"
                )
                del self._tickets[rid]
        for rid, owner in list(self._reply_owner.items()):
            if owner == r.idx:
                self._replies[rid] = (None, ReplicaDied(
                    f"replica {r.idx} died before replying"
                ))
                del self._reply_owner[rid]
        if self.metrics is not None:
            self.metrics.counter("router_replica_deaths")
        emit(
            f"[router] replica {r.idx} died; {len(failed_sids)} "
            "session(s) marked failed (sessions are never rerouted)"
        )

    def _dispatch(self, r: _Replica, msg: tuple) -> bool:
        kind, rid = msg[0], msg[1]
        if kind == "result":
            owner_tk = self._tickets.pop(rid, None)
            if owner_tk is None:
                return False
            tk = owner_tk[1]
            if msg[3] is not None:
                tk.error = _rebuild_error(*msg[3])
            else:
                tk.result = RemoteResult(msg[2])
                self.stats["serve_decisions"] += 1
                if tk.result.health_mask:
                    self.stats["serve_quarantines"] += 1
            return True
        if kind == "reply":
            self._reply_owner.pop(rid, None)
            self._replies[rid] = (msg[2], None)
            return True
        if kind == "reply_err":
            self._reply_owner.pop(rid, None)
            self._replies[rid] = (None, _rebuild_error(msg[2], msg[3]))
            return True
        return False

    def _drain(self) -> bool:
        moved = False
        for r in self._replicas:
            if r.dead:
                continue
            try:
                while r.conn.poll(0):
                    moved |= self._dispatch(r, r.conn.recv())
            except (EOFError, BrokenPipeError, OSError):
                if self._stopped:  # clean shutdown: EOF is expected
                    r.dead = True
                else:
                    self._mark_dead(r)
                moved = True
                continue
            # a replica exiting AFTER its stop-reply is a clean
            # shutdown, not a death — only an un-asked-for exit fails
            # its sessions
            if not self._stopped and not r.proc.is_alive():
                self._mark_dead(r)
                moved = True
        return moved

    def _call(self, r: _Replica, msg_tail: tuple,
              timeout_s: float = 120.0) -> Any:
        """One synchronous round-trip to a replica (create / close /
        set_params / metrics ...). Results for OTHER requests keep
        flowing while we wait — the pipes are drained, not blocked."""
        rid = self._next_rid()
        self._reply_owner[rid] = r.idx
        self._send(r, (msg_tail[0], rid, *msg_tail[1:]))
        deadline = time.monotonic() + timeout_s
        while rid not in self._replies:
            self._drain()
            if rid in self._replies:
                break
            if time.monotonic() > deadline:
                del self._reply_owner[rid]
                raise RuntimeError(
                    f"replica {r.idx} did not answer {msg_tail[0]!r} "
                    f"within {timeout_s:g}s"
                )
            time.sleep(2e-4)
        payload, err = self._replies.pop(rid)
        if err is not None:
            raise err
        return payload

    def _alive(self) -> list[_Replica]:
        return [r for r in self._replicas if not r.dead]

    # -- store facade ------------------------------------------------------

    def create(self, seed: int | None = None) -> int:
        """Place a new session on the least-loaded live replica;
        returns the GLOBAL session id (`gsid % n` names the owner for
        the session's whole life). Raises RuntimeError when the fleet
        is out of capacity — the store contract, so rotation and
        429-mapping work unchanged."""
        alive = self._alive()
        if not alive:
            self.stats["serve_capacity_rejections"] += 1
            raise RuntimeError("serve fleet has no live replicas")
        for r in sorted(alive, key=lambda r: r.sessions):
            try:
                payload = self._call(r, ("create", seed))
            except ReplicaDied:
                continue
            except RuntimeError as e:
                if "full" in str(e):
                    continue  # try the next-least-loaded replica
                raise
            lsid = payload["sid"]
            gsid = lsid * self.n + r.idx
            self._sid_map[gsid] = lsid
            self._failed.discard(gsid)
            r.sessions += 1
            return gsid
        self.stats["serve_capacity_rejections"] += 1
        if self.metrics is not None:
            self.metrics.counter("serve_capacity_rejections")
        raise RuntimeError(
            f"serve fleet full ({self.n} replicas); close sessions "
            "first"
        )

    def close(self, gsid: int) -> None:
        if gsid in self._failed:
            # the owning replica is gone: closing a failed session is
            # a no-op reclaim, not an error (the loadgen's teardown
            # closes every session it still holds)
            self._failed.discard(gsid)
            return
        lsid = self._sid_map.pop(gsid, None)
        if lsid is None:
            raise SessionError(f"unknown session {gsid}")
        r = self._replicas[self.replica_of(gsid)]
        if r.dead:
            return
        self._call(r, ("close", lsid))
        r.sessions -= 1

    def set_params(self, model_params, version: int | None = None,
                   origin: str = "swap", reason: str | None = None,
                   mark_good: bool = True) -> int:
        """Fleet-wide hot swap: broadcast the (host-materialized)
        pytree to every live replica, each of which applies it between
        compiled calls via `SessionStore.set_params` — zero recompiles
        on every member. Returns the applied version (identical across
        the fleet: the explicit `version` stamp, or each store's
        increment from a common history)."""
        from jax.tree_util import tree_map

        host_params = tree_map(np.asarray, model_params)
        applied = None
        for r in self._alive():
            try:
                out = self._call(r, (
                    "set_params", host_params, version, origin,
                    reason, mark_good,
                ))
            except ReplicaDied:
                continue
            applied = out["version"]
        if applied is None:
            raise RuntimeError("set_params: no live replicas")
        prev_version = self.params_version
        self.params_version = applied
        self.stats["serve_param_swaps"] += 1
        self.stats["serve_param_version"] = applied
        if self.metrics is not None:
            self.metrics.counter("serve_param_swaps")
            self.metrics.gauge("serve_param_version", applied)
        if self.runlog is not None:
            self.runlog.params_swap(
                applied, prev_version=prev_version,
                action=origin, reason=reason,
            )
        return applied

    def rollback_params(self, reason: str | None = None) -> int:
        applied = None
        for r in self._alive():
            try:
                out = self._call(r, ("rollback", reason))
            except ReplicaDied:
                continue
            applied = out["version"]
        if applied is None:
            raise RuntimeError("rollback_params: no live replicas")
        self.params_version = applied
        self.stats["serve_param_rollbacks"] += 1
        self.stats["serve_param_version"] = applied
        return applied

    def poison(self, gsid: int) -> None:
        """Test hook: trip the health sentinel on one session (see
        `_poison_session`)."""
        lsid = self._sid_map[gsid]
        self._call(self._replicas[self.replica_of(gsid)],
                   ("poison", lsid))

    def registry(self):
        """The fleet's merged `MetricsRegistry`: every live replica's
        registry folded together (counters add, histograms merge —
        the documented multi-worker aggregation path), plus the
        router's own, for one `/metrics` exposition."""
        from ..obs.metrics import MetricsRegistry

        agg = MetricsRegistry()
        for r in self._alive():
            try:
                reg, _stats = self._call(r, ("metrics",))
            except (ReplicaDied, RuntimeError):
                continue
            agg.merge(reg)
        if self.metrics is not None:
            agg.merge(self.metrics)
        return agg

    def fleet_stats(self) -> dict[str, int]:
        """Aggregated store stats across live replicas (ints summed),
        with the router's own counters riding along."""
        agg: dict[str, int] = dict(self.stats)
        for r in self._alive():
            try:
                _reg, stats = self._call(r, ("metrics",))
            except (ReplicaDied, RuntimeError):
                continue
            for k, v in stats.items():
                if isinstance(v, (int, float)):
                    agg[k] = agg.get(k, 0) + v
        return agg

    def replica_samples(self) -> list[dict[str, Any]]:
        """Per-replica labeled scrape (ISSUE 17): ONE `metrics`
        roundtrip per live replica returning each replica's OWN
        registry + store stats, unmerged — the fleet collector's and
        the labeled `/metrics` exposition's input. Dead replicas are
        reported (alive=False) rather than dropped, so the scoreboard
        shows the hole instead of silently shrinking."""
        out: list[dict[str, Any]] = []
        for r in self._replicas:
            sample: dict[str, Any] = {
                "replica": str(r.idx),
                "alive": not r.dead and r.proc.is_alive(),
                "sessions": r.sessions,
                "registry": None,
                "stats": None,
            }
            if sample["alive"]:
                try:
                    reg, stats = self._call(r, ("metrics",))
                    sample["registry"] = reg
                    sample["stats"] = stats
                except (ReplicaDied, RuntimeError):
                    sample["alive"] = False
            out.append(sample)
        return out

    # -- the fleet trajectory feed (ISSUE 18) ------------------------------

    def ring_pump(self, force: bool = False) -> int:
        """Fetch every live replica's accumulated ring chunks in ONE
        `ring_chunks` round-trip per replica and feed the fleet-level
        `collector`, remapping each chunk's whole `sid` array (and
        every close event) from the replica's local ids to the global
        space in one vectorized step — `gsid = lsid * n + idx`, the
        affinity map. `force=True` makes each replica drain its
        device rings first (the teardown / end-of-window path).
        Returns the number of records ingested. No-op without a
        collector."""
        if self.collector is None:
            return 0
        moved = 0
        for r in self._alive():
            try:
                ents = self._call(r, ("ring_chunks", bool(force)))
            except (ReplicaDied, RuntimeError):
                continue
            for ent in ents:
                if ent[0] == "chunk":
                    chunk = ent[1]
                    lsid = np.asarray(chunk.sid)
                    moved += int(lsid.shape[0])
                    self.collector.ingest_chunk(chunk.replace(
                        sid=(lsid * self.n + r.idx).astype(lsid.dtype)
                    ))
                else:  # ("close", lsid, quarantined)
                    self.collector.on_close(
                        int(ent[1]) * self.n + r.idx,
                        quarantined=bool(ent[2]),
                    )
        return moved

    def _maybe_ring_pump(self) -> None:
        """The `poll()`-cadence half: one fleet sweep per
        `ring_period_s`, so the pump loop that already drives the
        pipes ships trajectories too — no extra thread, no
        per-decision traffic."""
        if self.collector is None:
            return
        now = time.monotonic()
        if now >= self._ring_next:
            self._ring_next = now + self.ring_period_s
            self.ring_pump()

    # -- batching-front facade ---------------------------------------------

    def submit(self, gsid: int) -> RouterTicket:
        assert_owner(self, "serve-pump", "fleet-collector")
        tk = RouterTicket(gsid)
        if gsid in self._failed:
            tk.error = ReplicaDied(
                f"session {gsid}'s replica died; the session is "
                "failed, not rerouted"
            )
            return tk
        lsid = self._sid_map.get(gsid)
        if lsid is None:
            tk.error = SessionError(f"unknown session {gsid}")
            return tk
        r = self._replicas[self.replica_of(gsid)]
        if r.dead:
            tk.error = ReplicaDied(
                f"session {gsid}'s replica died; the session is "
                "failed, not rerouted"
            )
            return tk
        rid = self._next_rid()
        self._tickets[rid] = (r.idx, tk)
        try:
            self._send(r, ("submit", rid, lsid))
        except ReplicaDied:
            pass  # _mark_dead already errored the ticket
        return tk

    @property
    def pending(self) -> int:
        return len(self._tickets)

    def poll(self) -> bool:
        assert_owner(self, "serve-pump", "fleet-collector")
        moved = self._drain()
        self._maybe_ring_pump()
        return moved

    def flush(self, timeout_s: float = 120.0) -> None:
        deadline = time.monotonic() + timeout_s
        while self._tickets:
            if not self._drain():
                time.sleep(2e-4)
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"flush: {len(self._tickets)} request(s) still "
                    f"unresolved after {timeout_s:g}s"
                )

    # -- lifecycle ---------------------------------------------------------

    def stop(self, timeout_s: float = 30.0) -> None:
        """Drain and reap the fleet. Idempotent; stragglers are
        terminated."""
        if self._stopped:
            return
        self._stopped = True
        if self.collector is not None:
            try:  # last full sweep: no trajectory stranded in a ring
                self.ring_pump(force=True)
            except RuntimeError:
                pass
        for r in self._replicas:
            if r.dead or not r.proc.is_alive():
                continue
            try:
                self._call(r, ("stop",), timeout_s=timeout_s)
            except (RuntimeError, ReplicaDied):
                pass
        for r in self._replicas:
            if r.proc.is_alive():
                r.proc.join(timeout=timeout_s)
            if r.proc.is_alive():  # pragma: no cover - reap path
                r.proc.terminate()
                r.proc.join(timeout=5.0)
            try:
                r.conn.close()
            except OSError:
                pass

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
