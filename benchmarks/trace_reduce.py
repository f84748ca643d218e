"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark
reports: device busy and idle time, device time per named scope of the
program, collective time and the part of it no compute covers, the
operations that took most time and the longest idle gaps named by what
the host was doing. Reads the file with `jax.profiler.ProfileData`
alone.

A TPU's plane (`/device:TPU:n`) has one line of XLA operations
(`XLA Ops`): one event per executed HLO operation, named by its HLO
text, control flow (`while`, `conditional`, a called computation) as an
event that contains its body's events. An operation's
`jax.named_scope` path is in the HLO `op_name`, a string statistic of
the event's metadata entry (`xplane_meta.py` reads those), so a scope is
matched on that text. Which scopes: `KNOWN_SCOPES`, the program's
top-level ones, and whatever the caller hands in besides; the harness
hands in every `scope` value of `layer_metrics/*.json`, so a metric
over a scope the list lacks is a data file and no edit here. Times of
events that contain one another are never added twice: every sum here
is over a union of intervals or over self times.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINES = ("XLA Ops",)
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|all_reduce|all_gather|reduce_scatter|collective_permute|all_to_all"
    r"|psum|ppermute")
# the program's top-level scopes (`obs/tracing.annotate`): the default
# where a caller names none, and what "under no scope" is measured
# against. A sub-scope is ONE name that holds its parent's
# (`env/micro_step/drain`), so its time is its parent's too, and a
# metric over one comes as a data file (`scope_names`)
KNOWN_SCOPES = (
    "decima/gnn", "env/micro_step", "collect/scatter", "train/ppo_update",
    "serve/decide_batch", "serve/decide", "serve/dispatch", "serve/flush",
    "collect/observe", "collect/freeze", "collect/health",
    "decima/features", "decima/sample")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Disjoint, sorted intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: list[tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: list[tuple[float, float]], b: list[tuple[float, float]]
             ) -> list[tuple[float, float]]:
    """The part of the disjoint sorted intervals `a` that `b` (also
    disjoint and sorted) does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: list[dict]) -> list[float]:
    """For events of one line, each one's duration less what the events
    it contains cover. An event is inside another only if it ends
    within it: two that merely overlap are neighbours."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i]["start"], -events[i]["dur"]))
    out = [0.0] * len(events)
    stack: list[int] = []
    for i in order:
        e = events[i]
        end = e["start"] + e["dur"]
        while stack and (events[stack[-1]]["start"]
                         + events[stack[-1]]["dur"] < end):
            stack.pop()
        out[i] = e["dur"]
        if stack:
            out[stack[-1]] -= e["dur"]
        stack.append(i)
    return [max(v, 0.0) for v in out]


def short_name(hlo: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` as `fusion.12`."""
    return hlo.split(" = ", 1)[0].lstrip("%") if " = " in hlo else hlo


def _clip(events: list[dict], window: tuple[float, float]) -> list[dict]:
    """The events cut to the window (those outside it dropped)."""
    lo, hi = window
    out = []
    for e in events:
        a, b = max(e["start"], lo), min(e["start"] + e["dur"], hi)
        if b > a:
            out.append(dict(e, start=a, dur=b - a))
    return out


def scope_names(more=()) -> tuple[str, ...]:
    """`KNOWN_SCOPES` and the names in `more`, each once."""
    return tuple(dict.fromkeys(KNOWN_SCOPES + tuple(more)))


def scopes_in(text: str, scopes: tuple[str, ...] = KNOWN_SCOPES
              ) -> tuple[str, ...]:
    """Those of `scopes` in an operation's text, outermost first. A
    scope is matched as a substring, so a sub-scope's name is found
    where its parent's is: the longer one is the inner one."""
    found = [(text.rfind(s), len(s), s) for s in scopes if s in text]
    return tuple(s for _, _, s in sorted(found))


def reduce_events(device_ops: dict[int, list[dict]], host: list[dict], *,
                  window: tuple[float, float], chips: int,
                  host_spans: tuple[str, ...] = (),
                  unattributed: str = "host/other",
                  scopes: tuple[str, ...] = KNOWN_SCOPES) -> dict:
    """`device_ops[n]` are the operation events of device n: dicts with
    `name`, `start`, `dur` (seconds) and `text` (name and statistics as
    one string). `host` are host events (`name`, `start`, `dur`).
    `scopes` are the names looked for in an operation's text.
    `unscoped_s` is the busy time that no operation under any of them
    covers (the loop's own operations and the copies the compiler makes
    between scopes), averaged over the devices like a scope's time."""
    devices = sorted(device_ops)[:chips]
    if not devices:
        raise ValueError("the trace has no device plane")
    window_s = window[1] - window[0]
    device_ops = {d: _clip(device_ops[d], window) for d in devices}
    busy, by_name, unscoped, coll, coll_exposed = [], {}, 0.0, 0.0, 0.0
    ops_time: dict[str, float] = {}
    gaps_by: dict[str, float] = {}
    spans = sorted((h for h in host if h["name"] in host_spans),
                   key=lambda h: h["start"])
    known: dict[str, tuple[str, ...]] = {}  # texts repeat: look once
    for d in devices:
        evs = device_ops[d]
        ivs = union([(e["start"], e["start"] + e["dur"]) for e in evs])
        busy.append(total(ivs))
        by_scope: dict[str, list] = {}
        scoped: list[tuple[float, float]] = []
        for e in evs:
            if e["text"] not in known:
                known[e["text"]] = scopes_in(e["text"], scopes)
            iv = (e["start"], e["start"] + e["dur"])
            for s in known[e["text"]]:
                by_scope.setdefault(s, []).append(iv)
            if known[e["text"]]:
                scoped.append(iv)
        for s, iv in by_scope.items():
            by_name[s] = by_name.get(s, 0.0) + total(union(iv)) / len(devices)
        unscoped += total(subtract(ivs, union(scoped))) / len(devices)
        if d != devices[0]:
            continue
        c_iv = union([(e["start"], e["start"] + e["dur"]) for e in evs
                      if COLLECTIVE.search(e["name"])])
        selfs = self_times(evs)
        compute = union([
            (e["start"], e["start"] + e["dur"])
            for e, st in zip(evs, selfs)
            if not COLLECTIVE.search(e["name"]) and st >= e["dur"]])
        coll = total(c_iv)
        coll_exposed = total(subtract(c_iv, compute))
        for e, st in zip(evs, selfs):
            if st > 0:
                inner = known[e["text"]]
                k = f"{inner[-1]}:{e['name']}" if inner else e["name"]
                ops_time[k] = ops_time.get(k, 0.0) + st
        j = 0  # idle gaps and host spans both run forward in time
        for a, b in subtract([window], ivs):
            mid = 0.5 * (a + b)
            while j < len(spans) and (
                    spans[j]["start"] + spans[j]["dur"] < mid):
                j += 1
            name, k = unattributed, j
            while k < len(spans) and spans[k]["start"] <= mid:
                if mid <= spans[k]["start"] + spans[k]["dur"]:
                    name = spans[k]["name"]  # the innermost starts last
                k += 1
            gaps_by[name] = gaps_by.get(name, 0.0) + (b - a)
    top = sorted(ops_time.items(), key=lambda kv: -kv[1])
    gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "scopes": by_name,
        "unscoped_s": unscoped,
        "collective_s": coll,
        "collective_exposed_s": coll_exposed,
        "top_ops": [[k, v] for k, v in top[:10]],
        "gaps": [[k, v] for k, v in gaps[:10]],
        "device_events": sum(len(device_ops[d]) for d in devices),
    }


def load(path: str, window_span: str | None = None
         ) -> tuple[dict[int, list[dict]], list[dict], tuple[float, float]]:
    """Device operation events by device, the host's events, and the
    traced window in seconds: the span of the host event named
    `window_span` where the trace has one, else of all events."""
    from jax.profiler import ProfileData

    from benchmarks import xplane_meta

    if path.endswith(".textproto"):  # an XSpace written by hand (tests)
        with open(path) as fp:
            data = ProfileData.from_text_proto(fp.read())
        meta: dict = {}
    else:
        data = ProfileData.from_file(path)
        # a TPU's events carry times only: the scope path is a
        # statistic of the event's metadata entry
        meta = xplane_meta.event_metadata_text(path)
    device_ops: dict[int, list[dict]] = {}
    host: list[dict] = []
    lo, hi = float("inf"), float("-inf")
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        scope_of = meta.get(plane.name, {})
        for line in plane.lines:
            is_ops = m is not None and line.name in OPS_LINES
            for e in line.events:
                start, dur = e.start_ns * 1e-9, e.duration_ns * 1e-9
                if m is not None or plane.name.startswith("/host:CPU"):
                    lo, hi = min(lo, start), max(hi, start + dur)
                if is_ops:
                    text = " ".join(
                        [e.name, scope_of.get(e.name, "")]
                        + [v for _, v in e.stats if isinstance(v, str)])
                    device_ops.setdefault(int(m.group(2)), []).append(
                        {"name": short_name(e.name), "start": start,
                         "dur": dur, "text": text})
                elif m is None:
                    host.append({"name": e.name, "start": start, "dur": dur})
    marks = [h for h in host if h["name"] == window_span]
    if marks:
        lo, hi = marks[0]["start"], marks[0]["start"] + marks[0]["dur"]
    return device_ops, host, (lo, hi)


def reduce_file(path: str, *, chips: int, host_spans: tuple[str, ...] = (),
                unattributed: str = "host/other",
                window_span: str | None = None,
                scopes: tuple[str, ...] = KNOWN_SCOPES) -> dict:
    device_ops, host, window = load(path, window_span)
    return reduce_events(device_ops, host, window=window, chips=chips,
                         host_spans=host_spans, unattributed=unattributed,
                         scopes=scopes)
