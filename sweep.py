"""Sweep entry point: a scheduler over many episodes, scored by average
job completion time (`sparksched_tpu/sweep.py`):

    python sweep.py -f config/sweep_fair_demo.yaml

Prints the mean of the episodes' average job completion times and what
the sweep took, and writes one line an episode (lane, ordinal, average
JCT, jobs completed, makespan, decisions) to the file `sweep.out`
names.
"""

import csv
import os
import time
from functools import partial

from sparksched_tpu import sweep
from sparksched_tpu.config import enable_compilation_cache, load


def main(cfg: dict) -> dict:
    """Runs the sweep `cfg` describes and returns `sweep.run`'s
    results."""
    params, bank, scheduler = sweep.from_config(cfg)
    opts = cfg["sweep"]
    policy = None
    if opts.get("deterministic"):
        policy = partial(scheduler.batch_policy, deterministic=True)
    t0 = time.perf_counter()
    out = sweep.run(
        params, bank, scheduler, policy=policy,
        episodes=int(opts["episodes"]), lanes=int(opts["lanes"]),
        seed=int(opts.get("seed", 0)), rows=int(opts["rows_per_chunk"]))
    seconds = time.perf_counter() - t0
    n = len(out["avg_jct"])
    print(f"{scheduler.name}: mean avg job completion time = "
          f"{out['mean_avg_jct'] * 1e-3:.1f}s over {n} episodes "
          f"({out['decisions_total']} decisions in {out['chunks']} chunks, "
          f"{seconds:.1f}s, first call's compile included; "
          f"health_mask {out['telemetry']['health_mask']})")
    path = opts.get("out")
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        names = ("lane", "ordinal", "avg_jct", "jobs_completed", "makespan",
                 "decisions")
        with open(path, "w", newline="") as fp:
            w = csv.writer(fp)
            w.writerow(names)
            w.writerows(zip(*(out[k].tolist() for k in names)))
        print("wrote", path)
    return out


if __name__ == "__main__":
    enable_compilation_cache()
    main(load())
