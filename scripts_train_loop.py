"""Resumable training loop: runs PPO sessions of a few iterations each,
saving the full train state between sessions so progress survives kills.

Usage: python scripts_train_loop.py [max_sessions] [iters_per_session]
"""

import os.path as osp
import sys

from sparksched_tpu.config import enable_compilation_cache

enable_compilation_cache()

from flax import serialization  # noqa: E402
import jax  # noqa: E402

from sparksched_tpu.trainers import make_trainer  # noqa: E402
from scripts_train_session import ART, CFG  # noqa: E402


def run_sessions(
    max_sessions: int,
    iters: int,
    artifacts_dir: str = ART,
    out_path: str = "/root/repo/models/decima/model_tpu.msgpack",
    agent_overrides: dict | None = None,
) -> None:
    """Shared session loop (also used by scripts_finetune_loop)."""
    resume = osp.join(artifacts_dir, "train_state.msgpack")
    for s in range(max_sessions):
        agent = dict(CFG["agent"])
        # warm-start weights only matter before the first session; after
        # that resume_from restores params anyway — skip the torch
        # checkpoint conversion on every later session
        if agent_overrides and not osp.isfile(resume):
            agent |= agent_overrides
        cfg = {
            **CFG,
            "agent": agent,
            "trainer": {
                **CFG["trainer"],
                "num_iterations": iters,
                "artifacts_dir": artifacts_dir,
            },
        }
        t = make_trainer(cfg)
        state = t.train(
            resume_from=resume if osp.isfile(resume) else None
        )
        with open(out_path, "wb") as fp:
            fp.write(serialization.to_bytes(jax.device_get(state.params)))
        print(
            f"session {s + 1}/{max_sessions} done at iteration "
            f"{int(state.iteration)}",
            flush=True,
        )


if __name__ == "__main__":
    run_sessions(
        int(sys.argv[1]) if len(sys.argv) > 1 else 40,
        int(sys.argv[2]) if len(sys.argv) > 2 else 5,
    )
