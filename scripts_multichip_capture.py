"""Assemble MULTICHIP_r*.json from MEASURED sharded bench rows.

Round 6 replaces the dryrun ok/rc gate-check schema (MULTICHIP_r01..05)
with actual bench.py rows: for dp in {1, 2, 4, 8} this script runs the
headline bench on a virtual dp-device CPU mesh (BENCH_VIRTUAL_MESH) at
a fixed small lane count and records each run's full row — aggregate
dec/s in `value`, per-device dec/s + lanes in `per_device`, per-shard
lane-fit in `memory` — plus the dp=1 unsharded baseline. The rows are
honest CPU-virtual-mesh numbers (config.backend, `_cpu` metric suffix,
one physical core under all virtual devices: this measures that the
sharded program RUNS and what it costs, not multi-chip speedup). Rows
from real chips are not this script's: `chip_smoke.py --chips 4` is the
four-chip check.

Usage: python scripts_multichip_capture.py out.json
       (BENCH_NUM_ENVS to resize, def 64)
"""

import json
import os
import os.path as osp
import subprocess
import sys

REPO = osp.dirname(osp.abspath(__file__))
LANES = int(os.environ.get("BENCH_NUM_ENVS", 64))


def bench_row(dp: int) -> dict:
    """One bench.py run; the row is the last stdout line (bench prints
    comment lines with a leading '#'). Calibration is pinned to the
    flagship CPU knobs so all dp points measure the same program."""
    env = os.environ | {
        "BENCH_NUM_ENVS": str(LANES),
        "BENCH_BULK_EVENTS": "8",
        "BENCH_FULFILL_BULK": "1",
        "BENCH_BULK_CYCLES": "1",
        "JAX_PLATFORMS": "cpu",
    }
    argv = [sys.executable, "bench.py"]
    if dp > 1:
        env["BENCH_VIRTUAL_MESH"] = "1"
        argv += ["--mesh-dp", str(dp)]
    try:
        r = subprocess.run(
            argv, cwd=REPO, env=env, timeout=1200,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired as e:
        # record the timeout as this dp point's row and keep going —
        # one slow point must not lose the already-captured rows
        tail = (e.stderr or e.stdout or b"")
        if isinstance(tail, bytes):
            tail = tail.decode(errors="replace")
        return {"dp": dp, "error": "timeout=1200s", "tail": tail[-2000:]}
    if r.returncode != 0:
        return {"dp": dp, "error": f"rc={r.returncode}",
                "tail": (r.stderr or r.stdout)[-2000:]}
    rows = [
        ln for ln in r.stdout.splitlines()
        if ln.startswith("{") and '"metric"' in ln
    ]
    try:
        row = json.loads(rows[-1])
    except (IndexError, ValueError):
        # rc=0 but no parseable row line: record it as this dp point's
        # error row instead of crashing the sweep
        return {"dp": dp, "error": "no JSON row in bench stdout",
                "tail": r.stdout[-2000:]}
    row["dp"] = dp
    return row


def main() -> None:
    if len(sys.argv) < 2:
        sys.exit("usage: python scripts_multichip_capture.py out.json")
    out_path = sys.argv[1]
    rows = []
    for dp in (1, 2, 4, 8):
        print(f"# capturing dp={dp} at {LANES} lanes ...", flush=True)
        rows.append(bench_row(dp))
        v = rows[-1].get("value")
        pd = rows[-1].get("per_device", {}).get("steps_per_sec")
        print(f"#   dp={dp}: aggregate={v} per_device={pd}", flush=True)
    out = {
        "schema": "measured_rows_v2",
        "note": (
            "Measured sharded bench rows (bench.py --mesh-dp), replacing "
            "the r01-r05 dryrun ok/rc gate-check. virtual_mesh_cpu rows "
            "run all dp shards on one physical CPU — they prove the "
            "lane-sharded collect executes SPMD and carry its per-shard "
            "memory fit, not a hardware speedup claim (per-device FLOPs "
            "~1/dp is pinned in tests/test_parallel.py)."
        ),
        "global_lanes": LANES,
        "virtual_mesh_cpu": {"rows": rows},
    }
    with open(osp.join(REPO, out_path), "w") as fp:
        json.dump(out, fp, indent=1)
    print(f"wrote {out_path}", flush=True)


if __name__ == "__main__":
    main()
