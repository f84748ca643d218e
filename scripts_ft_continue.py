"""Fine-tune continuation with the round-4 corrected late-training
schedules.

The round-3 fine-tune (models/decima/model_ft.msgpack, warm-started
from the converted reference weights — the reference's own
state_dict_path workflow, reference schedulers/decima/scheduler.py:57-59)
is the repo's best overall artifact (+27.2% at the training setting,
+32.4% at the 50-job demo setting, EVAL.md/EVAL_50.md). This runner
continues it under the plateau recipe's fixed schedules
(scripts_plateau_train.py's diagnosis): low anneal-floored lr, a 0.01
entropy floor, tighter target_kl — probing whether the corrected
late-training regime extracts more from the already-strong policy.

Usage: python scripts_ft_continue.py [sessions] [iters_per_session]
Artifacts under artifacts/decima_ft_plateau; latest params also at
models/decima/model_ft_plateau.msgpack.
"""

import sys

sys.path.insert(0, "/root/repo")
from sparksched_tpu.config import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

FT_CKPT = "/root/repo/models/decima/model_ft.msgpack"


def make_cfg(iters: int) -> dict:
    from scripts_scratch_train import make_cfg as scratch_cfg

    cfg = scratch_cfg("ft_plateau", iters)
    cfg["trainer"] |= {
        "artifacts_dir": "/root/repo/artifacts/decima_ft_plateau",
        "entropy_coeff": 0.01,
        "entropy_anneal": None,
        "target_kl": 0.007,
        "opt_kwargs": {"lr": 6.0e-5},
        "lr_anneal": {"final": 2.0e-5, "steps": 1500},
    }
    cfg["agent"]["state_dict_path"] = FT_CKPT
    return cfg


def run(sessions: int, iters: int) -> None:
    from scripts_scratch_train import run_sessions

    run_sessions(
        make_cfg(iters),
        "/root/repo/models/decima/model_ft_plateau.msgpack",
        sessions,
        label="ft-continuation session",
    )


if __name__ == "__main__":
    run(
        int(sys.argv[1]) if len(sys.argv) > 1 else 4,
        int(sys.argv[2]) if len(sys.argv) > 2 else 25,
    )
