"""Generate the README-style Gantt comparison: fair vs (converted)
pretrained Decima on the same seed (reference README.md:5-7 figure).

Writes artifacts/gantt_fair.png, artifacts/gantt_decima.png (the
fine-tuned checkpoint) and artifacts/gantt_decima_scratch.png (the
from-scratch, no-warm-start checkpoint).
"""

import os
import sys

sys.path.insert(0, "/root/repo")

import examples  # noqa: E402

if __name__ == "__main__":
    n_jobs = int(sys.argv[1]) if len(sys.argv) > 1 else 50
    examples.ENV_CFG["max_jobs"] = n_jobs
    os.makedirs("/root/repo/artifacts", exist_ok=True)
    os.chdir("/root/repo/artifacts")
    for name, ckpt, out in [
        ("fair", None, "gantt_fair.png"),
        # the tpu fine-tuned checkpoint — this framework's best model
        # (EVAL_50.md: beats both fair and the converted reference ckpt)
        ("decima", "/root/repo/models/decima/model_ft.msgpack",
         "gantt_decima.png"),
        # the from-scratch (no warm start) checkpoint — the policy this
        # framework's own PPO produced (EVAL_50.md: +28.4% vs fair)
        ("decima", "/root/repo/models/decima/model_tpu.msgpack",
         "gantt_decima_scratch.png"),
    ]:
        sched = examples.make_scheduler(name, ckpt)
        avg = examples.run_episode(
            sched, seed=7, render=True, max_steps=6000
        )
        os.rename("screenshot.png", out)
        print(f"{name}: avg JCT {avg * 1e-3:.1f}s -> {out}", flush=True)
