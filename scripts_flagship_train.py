"""Flagship-scale PPO training runner (config/decima_tpch.yaml: 50
executors, 200-job cap, 9600-step rollouts — the reference's headline
training configuration, reference config/decima_tpch.yaml:80-87).

Resumable sessions like scripts_scratch_train.py: the full train state
(params + optimizer + RNG + iteration) is saved between sessions, so
progress accumulates across sessions. Adds the round-3
training-stability levers that made the from-scratch small-scale run
beat fair (entropy/lr anneal — see
scripts_scratch_train.py's recipe notes).

Usage: python scripts_flagship_train.py [sessions] [iters_per_session]
Artifacts under artifacts/decima_flagship; latest params also written to
models/decima/model_flagship.msgpack. Evaluate with
  EVAL_EXECS=50 EVAL_JOBS=50 python scripts_eval_decima.py 24 \
      models/decima/model_flagship.msgpack EVAL_FLAGSHIP.md
"""

import os.path as osp
import sys

sys.path.insert(0, "/root/repo")
from sparksched_tpu.config import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import yaml  # noqa: E402
import jax  # noqa: E402

ART = "/root/repo/artifacts/decima_flagship"


def make_cfg(iters: int) -> dict:
    with open(osp.join(osp.dirname(__file__),
                       "config/decima_tpch.yaml")) as fp:
        cfg = yaml.safe_load(fp)
    num_epochs = 1 if jax.default_backend() == "cpu" else 3
    cfg["trainer"] |= {
        "num_iterations": iters,
        "artifacts_dir": ART,
        "checkpointing_freq": 5,
        "use_tensorboard": False,
        "num_epochs": num_epochs,
        # round-3 stability levers (scripts_scratch_train.py recipe),
        # with the entropy floor raised to 0.01: the r3 from-scratch
        # curve's post-peak decay window coincided with the coefficient
        # annealing below ~0.01 (scripts_plateau_train.py's diagnosis)
        "entropy_anneal": {"final": 0.01, "iterations": 400},
        "lr_anneal": {"final": 1.0e-4, "steps": 15000},
        "profiling": True,
    }
    return cfg


def run(sessions: int, iters: int) -> None:
    from scripts_scratch_train import run_sessions

    run_sessions(
        make_cfg(iters),
        "/root/repo/models/decima/model_flagship.msgpack",
        sessions,
        label="flagship session",
    )


if __name__ == "__main__":
    run(
        int(sys.argv[1]) if len(sys.argv) > 1 else 10,
        int(sys.argv[2]) if len(sys.argv) > 2 else 5,
    )
