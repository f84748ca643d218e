"""Fused-vs-unfused bulk-pass CPU A/B at the recorded headline configs
(ISSUE 7 acceptance): run bench.py twice per config — identical pinned
knobs, `BENCH_BULK_FUSED` flipped — and write the four rows plus the
computed speedups to `artifacts/fused_ab_r07.json`.

Configs are the two CPU rows PERF_ROUNDS.md has tracked across rounds:

- 8 lanes,   be=8 fb=1 bc=1  (the round-4 fused-pop A/B config)
- 256 lanes, be=8 fb=1 bc=1  (the round-4/5 contended-box config)

Knobs are PINNED (no self-calibration) so the pair differs in exactly
one bit; every row still stamps its full config + telemetry, so the
artifact is self-describing. CPU-pinned: this is the evidence A/B —
the on-chip confirmation slot is chip-session stage 13.

Usage: python scripts_fused_ab.py [--quick]
  --quick drops the 256-lane pair (each 256-lane bench run costs
  minutes on the 1-core box).
"""

from __future__ import annotations

import json
import os
import os.path as osp
import subprocess
import sys

REPO = osp.dirname(osp.abspath(__file__))

# reps: the 8-lane timed window is seconds long on this box and its
# single-run numbers swing ~±10% — interleave fused/unfused reps and
# take per-arm medians so the recorded speedup is not one draw of that
# noise; the 256-lane window is long enough that one rep is stable
CONFIGS = [
    # 16 chunks: the 8-lane default window is seconds long and swings
    # ±20% run-to-run on this box — a 4x window + median-of-3 makes
    # the recorded speedup a measurement, not a draw
    {"name": "8lane_be8_fb1_bc1", "BENCH_NUM_ENVS": "8",
     "BENCH_NUM_CHUNKS": "16", "reps": 3},
    {"name": "256lane_be8_fb1_bc1", "BENCH_NUM_ENVS": "256", "reps": 1},
]

PINNED = {
    "JAX_PLATFORMS": "cpu",
    "BENCH_BULK_EVENTS": "8",
    "BENCH_FULFILL_BULK": "1",
    "BENCH_BULK_CYCLES": "1",
    # telemetry on: the A/B rows double as phase-rank inputs
    "BENCH_TELEMETRY": "1",
    # the analysis/memory stamps cost minutes per row on this box and
    # are identical across the pair — stamp once via the normal bench
    # path instead of four times here
    "BENCH_ANALYSIS": "0",
    "BENCH_MEMFIT": "0",
}


def run_row(extra_env: dict) -> dict | None:
    env = os.environ | PINNED | extra_env
    r = subprocess.run(
        [sys.executable, osp.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=3600,
    )
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    print(
        f"# fused_ab: no row (rc={r.returncode}): "
        f"{r.stderr.strip().splitlines()[-1:] if r.stderr else ''}",
        file=sys.stderr, flush=True,
    )
    return None


def main() -> int:
    quick = "--quick" in sys.argv
    out = {"configs": {}}
    for cfg in CONFIGS[: 1 if quick else None]:
        name = cfg["name"]
        reps = int(cfg.get("reps", 1))
        envs = {
            k: v for k, v in cfg.items() if k not in ("name", "reps")
        }
        rows = {"fused": [], "unfused": []}
        for rep in range(reps):
            # interleave arms so slow machine-state drift (page cache,
            # the sibling service's bursts) hits both equally
            for fused in ("1", "0"):
                arm = "fused" if fused == "1" else "unfused"
                print(
                    f"# fused_ab: {name} {arm} rep {rep + 1}/{reps}",
                    file=sys.stderr, flush=True,
                )
                row = run_row(envs | {"BENCH_BULK_FUSED": fused})
                if row is None:
                    return 1
                rows[arm].append(row)

        def median(arm):
            vs = sorted(r["value"] for r in rows[arm])
            return vs[len(vs) // 2]

        v_f, v_u = median("fused"), median("unfused")
        out["configs"][name] = {
            # the rows whose value IS the reported median, plus every
            # rep's value so the spread is on record
            "fused": next(
                r for r in rows["fused"] if r["value"] == v_f
            ),
            "unfused": next(
                r for r in rows["unfused"] if r["value"] == v_u
            ),
            "fused_reps": [r["value"] for r in rows["fused"]],
            "unfused_reps": [r["value"] for r in rows["unfused"]],
            "speedup": round(v_f / v_u, 3) if v_u else None,
        }
        print(
            f"# fused_ab: {name}: fused {v_f} vs unfused {v_u} dec/s "
            f"({100 * (v_f / v_u - 1):+.1f}%, median of {reps})",
            file=sys.stderr, flush=True,
        )
    os.makedirs(osp.join(REPO, "artifacts"), exist_ok=True)
    # quick runs must not clobber the full two-config artifact
    path = osp.join(
        REPO, "artifacts",
        "fused_ab_r07_quick.json" if quick else "fused_ab_r07.json",
    )
    with open(path, "w") as fp:
        json.dump(out, fp, indent=1)
    print(f"# fused_ab: wrote {path}", file=sys.stderr, flush=True)
    for name, c in out["configs"].items():
        print(json.dumps({
            "metric": f"fused_ab_{name}",
            "speedup": c["speedup"],
            "fused": c["fused"]["value"],
            "unfused": c["unfused"]["value"],
            "unit": "steps/s",
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
