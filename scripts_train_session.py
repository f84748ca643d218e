"""One bounded TPU training session that resumes from the saved train
state if present (driven repeatedly to accumulate long training runs
within the environment's per-process time limits)."""
import os.path as osp
import sys

sys.path.insert(0, "/root/repo")
from sparksched_tpu.config import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

from flax import serialization  # noqa: E402
import jax  # noqa: E402

from sparksched_tpu.trainers import make_trainer  # noqa: E402

ART = "/root/repo/artifacts/decima_tpu"
CFG = {
    "trainer": {
        "trainer_cls": "PPO", "num_iterations": 40, "num_sequences": 2,
        "num_rollouts": 4, "seed": 42, "artifacts_dir": ART,
        "checkpointing_freq": 20, "use_tensorboard": False,
        "num_epochs": 3, "num_batches": 10, "clip_range": 0.2,
        "target_kl": 0.01, "entropy_coeff": 0.04, "beta_discount": 5.0e-3,
        "opt_cls": "Adam", "opt_kwargs": {"lr": 3.0e-4},
        "max_grad_norm": 0.5, "rollout_steps": 600,
    },
    "agent": {
        "agent_cls": "DecimaScheduler", "embed_dim": 16,
        "gnn_mlp_kwargs": {"hid_dims": [32, 16], "act_cls": "LeakyReLU",
                            "act_kwargs": {"negative_slope": 0.2}},
        "policy_mlp_kwargs": {"hid_dims": [64, 64], "act_cls": "Tanh"},
    },
    "env": {
        "num_executors": 10, "job_arrival_cap": 20, "moving_delay": 2000.0,
        "mean_time_limit": 2.0e7, "job_arrival_rate": 4.0e-5,
        "warmup_delay": 1000.0,
    },
}

if __name__ == "__main__":
    t = make_trainer(CFG)
    resume = osp.join(ART, "train_state.msgpack")
    state = t.train(resume_from=resume if osp.isfile(resume) else None)
    with open("/root/repo/models/decima/model_tpu.msgpack", "wb") as fp:
        fp.write(serialization.to_bytes(jax.device_get(state.params)))
    print("session done at iteration", int(state.iteration), flush=True)
