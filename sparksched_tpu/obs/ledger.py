"""Perf-regression ledger: an append-only index over the repo's bench
artifacts across rounds (ISSUE 17, tentpole part 3).

The bench series (`BENCH_rNN.json` at the repo root, `artifacts/*.json`
per subsystem) is the project's perf memory, but nothing reads it back:
a PR that regresses the serving headline ships silently unless a human
diffs JSON by hand. The ledger closes that loop:

- **Index**: schema-tolerant extraction over every `artifacts/*.json` +
  `BENCH_*.json`. Three extractors, in order: (1) any dict anywhere in
  the document carrying a string `metric` and numeric `value` is a row
  (the r10+ row dialect, BENCH `parsed` blocks, fused_ab config pairs,
  MULTICHIP measured rows); (2) `sustained_rps_slo`-style headline
  dicts ({front: rps}) become synthetic `sustained_rps_slo_<front>`
  entries; (3) files yielding nothing (protocol-only artifacts like
  `online_loop_r16.json`) fall back to shallow numeric leaves named by
  their dotted path, so *every* parseable file contributes entries and
  "full parse coverage" is checkable (files_failed == 0 and every file
  indexed).
- **Rounds**: inferred from the `_rNN` filename stamp; a file without
  one gets round -1 (indexed, excluded from trends).
- **Noise bands**: each entry's band comes from its own artifact — the
  paired-rep lists the A/B protocol stamps (`ab.goodput_rps_reps`,
  `*_reps`) give (min, max) of reps; entries without reps get a
  DEFAULT_REL_BAND half-width. Bands travel with the entry, so the
  verdict never invents a tolerance the measurement didn't earn.
- **Verdicts**: for each metric family observed in >= 2 rounds, compare
  the latest entry against the previous round's. Direction comes from
  the unit (rates are higher-better, latencies lower-better; unknown
  units are trend-only). REGRESSION only when the bands are DISJOINT in
  the bad direction (latest's most favorable edge worse than previous'
  least favorable edge) — i.e. outside the noise band, the PERF_ROUNDS.md
  operational-rule standard. IMPROVEMENT is the mirror; else STABLE.

CLI (`python -m sparksched_tpu.obs.ledger`): prints the trend report,
checks `--pin metric=value` headline assertions, and exits nonzero on
parse-coverage failure (rc 2), pin mismatch (rc 3), or a regression
verdict (rc 4) — the tier-1 gate wires exactly this.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
from typing import Any

# default relative half-width when an entry carries no paired reps:
# generous enough to absorb single-run jitter on a noisy box, tight
# enough that a real headline drop (the r13 100 -> 125 scale) is
# orders beyond it
DEFAULT_REL_BAND = 0.05
# floor on any band's half-width: 3-rep lists rounded to 2 decimals
# can collapse to zero width, and a zero-width band turns sub-percent
# jitter into a REGRESSION verdict
MIN_REL_BAND = 0.01
# committed waiver file: {"waivers": {metric: reason}} acknowledges a
# verdict-visible drop that is a protocol change, not a perf loss
# (e.g. r18 re-measured sustained rps WITH the network tier's wire
# cost on the 1-core box — ROADMAP item 2)
WAIVERS_FILE = "ledger_waivers.json"

ROUND_RE = re.compile(r"_r(\d+)")

# unit direction: which way is "worse". Rates up = good, latencies
# up = bad; anything unrecognized is indexed but never judged.
_HIGHER_BETTER = ("steps/s", "rps", "decisions/s", "dec/s", "req/s",
                  "sessions/s", "/s")
_LOWER_BETTER = ("ms", "us", "s", "bytes", "mb", "gb")


def unit_direction(unit: str) -> int:
    """+1 higher-better, -1 lower-better, 0 unknown."""
    u = (unit or "").strip().lower()
    if not u:
        return 0
    for suf in _HIGHER_BETTER:
        if u.endswith(suf):
            return 1
    if u in _LOWER_BETTER:
        return -1
    return 0


class Entry:
    """One indexed measurement: (round, file, metric, value, unit,
    noise band). `band` is the (lo, hi) envelope of the measurement's
    own paired reps, or a DEFAULT_REL_BAND half-width."""

    __slots__ = ("round", "file", "metric", "value", "unit", "band",
                 "band_source", "path")

    def __init__(self, rnd: int, file: str, metric: str, value: float,
                 unit: str = "", band: tuple[float, float] | None = None,
                 band_source: str = "default", path: str = "") -> None:
        self.round = rnd
        self.file = file
        self.metric = metric
        self.value = float(value)
        self.unit = unit
        if band is None:
            half = abs(self.value) * DEFAULT_REL_BAND
            band = (self.value - half, self.value + half)
            band_source = "default"
        floor = abs(self.value) * MIN_REL_BAND
        band = (min(band[0], self.value - floor),
                max(band[1], self.value + floor))
        self.band = (float(band[0]), float(band[1]))
        self.band_source = band_source
        self.path = path

    def to_json(self) -> dict[str, Any]:
        return {
            "round": self.round, "file": self.file,
            "metric": self.metric, "value": self.value,
            "unit": self.unit, "band": list(self.band),
            "band_source": self.band_source, "path": self.path,
        }


def _is_num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) \
        and math.isfinite(v)


def _rep_lists(obj: Any, depth: int = 0) -> dict[str, list[float]]:
    """All `*_reps` numeric lists reachable within a row (shallow)."""
    out: dict[str, list[float]] = {}
    if depth > 3 or not isinstance(obj, dict):
        return out
    for k, v in obj.items():
        if (k.endswith("_reps") and isinstance(v, list) and v
                and all(_is_num(x) for x in v)):
            out[k] = [float(x) for x in v]
        elif isinstance(v, dict):
            out.update(_rep_lists(v, depth + 1))
    return out


def _band_from_row(row: dict[str, Any], value: float
                   ) -> tuple[tuple[float, float], str] | None:
    """The row's own noise band: the `*_reps` list whose envelope
    contains (or whose median equals) the row value — the paired-rep
    A/B protocol's rep vector. None when the row carries no reps."""
    for name, reps in _rep_lists(row).items():
        lo, hi = min(reps), max(reps)
        med = sorted(reps)[len(reps) // 2]
        if lo - 1e-9 <= value <= hi + 1e-9 or \
                math.isclose(med, value, rel_tol=1e-6):
            return (lo, hi), name
    return None


def _walk_rows(obj: Any, path: str, out: list[tuple[str, dict]],
               depth: int = 0) -> None:
    """Collect every dict with a string `metric` + numeric `value`."""
    if depth > 8:
        return
    if isinstance(obj, dict):
        if isinstance(obj.get("metric"), str) and _is_num(obj.get("value")):
            out.append((path, obj))
        for k, v in obj.items():
            _walk_rows(v, f"{path}.{k}" if path else str(k), out,
                       depth + 1)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            _walk_rows(v, f"{path}[{i}]", out, depth + 1)


def _walk_headlines(obj: Any, path: str,
                    out: list[tuple[str, str, float]],
                    depth: int = 0) -> None:
    """`sustained_rps_slo`-style headline dicts: {label: number} under
    a known headline key become synthetic `<key>_<label>` entries."""
    if depth > 6 or not isinstance(obj, dict):
        return
    for k, v in obj.items():
        if k == "sustained_rps_slo" and isinstance(v, dict):
            for label, num in v.items():
                if _is_num(num):
                    out.append((f"{path}.{k}" if path else k,
                                f"{k}_{label}", float(num)))
        elif isinstance(v, dict):
            _walk_headlines(v, f"{path}.{k}" if path else str(k), out,
                            depth + 1)


def _numeric_leaves(obj: Any, path: str = "", depth: int = 0
                    ) -> list[tuple[str, float]]:
    """Shallow numeric leaves (the zero-row fallback). Depth-limited so
    protocol-only artifacts still contribute a handful of entries."""
    out: list[tuple[str, float]] = []
    if depth > 2:
        return out
    if isinstance(obj, dict):
        for k, v in obj.items():
            p = f"{path}.{k}" if path else str(k)
            if _is_num(v):
                out.append((p, float(v)))
            elif isinstance(v, dict):
                out.extend(_numeric_leaves(v, p, depth + 1))
    return out


def round_of(path: str) -> int:
    m = None
    for m in ROUND_RE.finditer(os.path.basename(path)):
        pass
    return int(m.group(1)) if m else -1


def extract_file(path: str) -> list[Entry]:
    """Index one artifact. Raises on unparseable JSON (the coverage
    gate counts those); returns >= 1 entry for any parseable dict."""
    with open(path) as fp:
        doc = json.load(fp)
    rnd = round_of(path)
    fname = os.path.relpath(path)
    entries: list[Entry] = []

    rows: list[tuple[str, dict]] = []
    _walk_rows(doc, "", rows)
    for rpath, row in rows:
        value = float(row["value"])
        band = _band_from_row(row, value)
        entries.append(Entry(
            rnd, fname, str(row["metric"]), value,
            unit=str(row.get("unit", "")),
            band=band[0] if band else None,
            band_source=band[1] if band else "default",
            path=rpath,
        ))
        # ISSUE 20: a row stamped with an `attribution` block also
        # indexes its windowed per-segment p99s — a PR that shifts the
        # tail from device_compute into queue_wait now regresses a
        # TRACKED metric even when the headline survives
        att = row.get("attribution")
        seg_p99 = (att.get("seg_p99_ms")
                   if isinstance(att, dict) else None)
        if isinstance(seg_p99, dict):
            for seg, sv in sorted(seg_p99.items()):
                if _is_num(sv):
                    entries.append(Entry(
                        rnd, fname,
                        f"{row['metric']}_seg_{seg}_p99_ms",
                        float(sv), unit="ms",
                        path=f"{rpath}.attribution.seg_p99_ms.{seg}",
                    ))

    heads: list[tuple[str, str, float]] = []
    _walk_headlines(doc, "", heads)
    seen = {e.metric for e in entries}
    for hpath, metric, value in heads:
        if metric not in seen:
            entries.append(Entry(rnd, fname, metric, value,
                                 unit="rps", path=hpath))
            seen.add(metric)

    if not entries and isinstance(doc, dict):
        for lpath, value in _numeric_leaves(doc)[:16]:
            entries.append(Entry(rnd, fname, lpath, value, unit="",
                                 path=lpath))
    return entries


class Ledger:
    """The full index plus coverage accounting."""

    def __init__(self) -> None:
        self.entries: list[Entry] = []
        self.files_ok: list[str] = []
        self.files_failed: list[tuple[str, str]] = []
        self.waivers: dict[str, str] = {}

    @classmethod
    def scan(cls, artifacts_dir: str = "artifacts",
             bench_glob: str = "BENCH_*.json",
             root: str = ".") -> "Ledger":
        led = cls()
        wpath = os.path.join(root, artifacts_dir, WAIVERS_FILE)
        if os.path.exists(wpath):
            with open(wpath) as fp:
                led.waivers = dict(json.load(fp).get("waivers", {}))
        paths = sorted(glob.glob(os.path.join(root, artifacts_dir,
                                              "*.json")))
        paths += sorted(glob.glob(os.path.join(root, bench_glob)))
        paths = [p for p in paths
                 if os.path.basename(p) != WAIVERS_FILE]
        for p in paths:
            try:
                got = led.extend(p)
            except Exception as exc:  # noqa: BLE001 — coverage report
                led.files_failed.append((p, f"{type(exc).__name__}: {exc}"))
                continue
            if not got:
                led.files_failed.append((p, "no entries extracted"))
        return led

    def extend(self, path: str) -> int:
        es = extract_file(path)
        if es:
            self.entries.extend(es)
            self.files_ok.append(path)
        return len(es)

    # -- reads ---------------------------------------------------------

    def families(self) -> dict[str, list[Entry]]:
        """metric -> entries sorted by round (stable within a round)."""
        fams: dict[str, list[Entry]] = {}
        for e in self.entries:
            fams.setdefault(e.metric, []).append(e)
        for es in fams.values():
            es.sort(key=lambda e: e.round)
        return fams

    def verdicts(self) -> list[dict[str, Any]]:
        """Latest-vs-previous-round comparison per multi-round family.
        Outside-the-noise-band means the two bands are disjoint in the
        bad direction."""
        out: list[dict[str, Any]] = []
        for metric, es in sorted(self.families().items()):
            rounds = sorted({e.round for e in es if e.round >= 0})
            if len(rounds) < 2:
                continue
            cur = [e for e in es if e.round == rounds[-1]][-1]
            prev = [e for e in es if e.round == rounds[-2]][-1]
            direction = unit_direction(cur.unit) or \
                unit_direction(prev.unit)
            if direction == 0:
                continue
            if direction > 0:
                regressed = cur.band[1] < prev.band[0]
                improved = cur.band[0] > prev.band[1]
            else:
                regressed = cur.band[0] > prev.band[1]
                improved = cur.band[1] < prev.band[0]
            verdict = ("REGRESSION" if regressed
                       else "IMPROVEMENT" if improved else "STABLE")
            if verdict == "REGRESSION" and metric in self.waivers:
                verdict = "WAIVED"
            out.append({
                "metric": metric, "verdict": verdict,
                "direction": "higher" if direction > 0 else "lower",
                "prev_round": prev.round, "prev_value": prev.value,
                "prev_band": list(prev.band),
                "round": cur.round, "value": cur.value,
                "band": list(cur.band),
                "prev_file": prev.file, "file": cur.file,
                "waived": self.waivers.get(metric),
            })
        return out

    def trend_report(self) -> str:
        lines = ["# Perf ledger trend report",
                 f"files indexed: {len(self.files_ok)}  "
                 f"failed: {len(self.files_failed)}  "
                 f"entries: {len(self.entries)}", ""]
        for p, why in self.files_failed:
            lines.append(f"PARSE FAIL  {p}: {why}")
        if self.files_failed:
            lines.append("")
        fams = self.families()
        multi = {m: es for m, es in fams.items()
                 if len({e.round for e in es if e.round >= 0}) > 1}
        lines.append(f"## Trends ({len(multi)} multi-round metric "
                     f"families of {len(fams)})")
        for metric in sorted(multi):
            es = multi[metric]
            pts = " -> ".join(
                f"r{e.round:02d}:{e.value:g}" for e in es
                if e.round >= 0
            )
            unit = next((e.unit for e in es if e.unit), "")
            lines.append(f"  {metric} [{unit}]: {pts}")
        lines.append("")
        vs = self.verdicts()
        bad = [v for v in vs if v["verdict"] == "REGRESSION"]
        lines.append(f"## Verdicts ({len(vs)} judged, "
                     f"{len(bad)} regressions)")
        for v in vs:
            if v["verdict"] == "STABLE":
                continue
            lines.append(
                f"  {v['verdict']:<11} {v['metric']}: "
                f"r{v['prev_round']:02d} {v['prev_value']:g} "
                f"(band {v['prev_band'][0]:g}..{v['prev_band'][1]:g})"
                f" -> r{v['round']:02d} {v['value']:g} "
                f"(band {v['band'][0]:g}..{v['band'][1]:g})"
                + (f"  [waived: {v['waived']}]" if v.get("waived")
                   else "")
            )
        return "\n".join(lines) + "\n"

    def check_pins(self, pins: list[tuple[str, float, float]]
                   ) -> list[str]:
        """Headline pins: (metric[@rNN], value, abs_tol). A metric
        with an `@rNN` suffix pins that ROUND's entry (the headline
        rows live at their measurement round — r17's 125 rps stays
        pinned even after later rounds re-measure under different
        protocols); without it the latest round is checked. Returns
        failure strings (empty = all pins hold)."""
        fails = []
        fams = self.families()
        for spec, want, tol in pins:
            metric, _, rnd_s = spec.partition("@")
            es = fams.get(metric)
            if not es:
                fails.append(f"pin {spec}: no such metric in index")
                continue
            if rnd_s:
                rnd = int(rnd_s.lstrip("r"))
                es = [e for e in es if e.round == rnd]
                if not es:
                    fails.append(
                        f"pin {spec}: metric {metric} has no "
                        f"round-{rnd} entry")
                    continue
            e = es[-1]
            if abs(e.value - want) > tol:
                fails.append(
                    f"pin {spec}: want {want:g} +-{tol:g}, "
                    f"index has {e.value:g} (r{e.round:02d}, "
                    f"{e.file})"
                )
        return fails


def _parse_pin(s: str) -> tuple[str, float, float]:
    """--pin metric[@rNN]=value[:tol]"""
    name, _, rest = s.partition("=")
    if not rest:
        raise argparse.ArgumentTypeError(
            f"pin {s!r}: expected metric[@rNN]=value[:tol]")
    val, _, tol = rest.partition(":")
    return name, float(val), float(tol) if tol else 1e-6


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparksched_tpu.obs.ledger",
        description="Index bench artifacts across rounds, report "
                    "trends, and fail on out-of-band regressions.")
    ap.add_argument("--root", default=".", help="repo root to scan")
    ap.add_argument("--artifacts", default="artifacts",
                    help="artifacts dir (relative to --root)")
    ap.add_argument("--bench-glob", default="BENCH_*.json",
                    help="root-level bench series glob")
    ap.add_argument("--pin", action="append", type=_parse_pin,
                    default=[], metavar="METRIC=VALUE[:TOL]",
                    help="assert a headline row is present at VALUE")
    ap.add_argument("--json", default=None,
                    help="also dump the full index as JSON here")
    ap.add_argument("--no-strict-coverage", action="store_true",
                    help="don't fail on unparseable/empty files")
    ap.add_argument("--no-verdicts", action="store_true",
                    help="report trends only, never rc 4")
    args = ap.parse_args(argv)

    from sparksched_tpu.obs.runlog import emit

    led = Ledger.scan(artifacts_dir=args.artifacts,
                      bench_glob=args.bench_glob, root=args.root)
    report = led.trend_report()
    emit(report.rstrip("\n"))

    if args.json:
        with open(args.json, "w") as fp:
            json.dump({
                "entries": [e.to_json() for e in led.entries],
                "files_ok": led.files_ok,
                "files_failed": led.files_failed,
                "verdicts": led.verdicts(),
            }, fp, indent=1)

    rc = 0
    if led.files_failed and not args.no_strict_coverage:
        emit(f"COVERAGE FAIL: {len(led.files_failed)} file(s) "
             "unindexed")
        rc = 2
    pin_fails = led.check_pins(args.pin)
    for f in pin_fails:
        emit(f"PIN FAIL: {f}")
    if pin_fails:
        rc = rc or 3
    if not args.no_verdicts:
        bad = [v for v in led.verdicts()
               if v["verdict"] == "REGRESSION"]
        for v in bad:
            emit(f"REGRESSION: {v['metric']} r{v['prev_round']:02d} "
                 f"{v['prev_value']:g} -> r{v['round']:02d} "
                 f"{v['value']:g} (outside noise band)")
        if bad:
            rc = rc or 4
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
