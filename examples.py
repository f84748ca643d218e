"""Demo episodes (reference examples.py:15-106):

    python examples.py --sched fair
    python examples.py --sched decima [--state-dict PATH]
    python examples.py --sched random

Runs one 50-job / 10-executor TPC-H episode with the chosen scheduler,
prints the average job duration, and saves a Gantt chart to
`screenshot.png` (the reference renders live with pygame and saves the
same screenshot on close; here the chart is drawn headlessly)."""

from __future__ import annotations

from argparse import ArgumentParser

import jax
import jax.numpy as jnp

from sparksched_tpu import metrics
from sparksched_tpu.config import EnvParams
from sparksched_tpu.env import core
from sparksched_tpu.env.observe import observe
from sparksched_tpu.renderer import GanttRenderer
from sparksched_tpu.schedulers import (
    DecimaScheduler,
    RandomScheduler,
    RoundRobinScheduler,
)
from sparksched_tpu.workload import make_workload_bank

ENV_CFG = {
    "num_executors": 10,
    "max_jobs": 50,
    "moving_delay": 2000.0,
    "warmup_delay": 1000.0,
    "job_arrival_rate": 4e-5,
}


# shipped checkpoint, loaded when --state-dict is omitted: the
# reference demo auto-loads its published weights the same way
# (reference examples.py:69, models/decima/model.pt)
DEFAULT_DECIMA_CKPT = "models/decima/model_tpu.msgpack"


def make_scheduler(name: str, state_dict: str | None):
    n = ENV_CFG["num_executors"]
    if name == "fair":
        return RoundRobinScheduler(n, dynamic_partition=True)
    if name == "fifo":
        return RoundRobinScheduler(n, dynamic_partition=False)
    if name == "random":
        return RandomScheduler()
    if name == "decima":
        if state_dict is None:
            import os.path as osp

            state_dict = osp.join(
                osp.dirname(osp.abspath(__file__)), DEFAULT_DECIMA_CKPT
            )
            print(f"loading shipped checkpoint {DEFAULT_DECIMA_CKPT} "
                  "(override with --state-dict)")
        return DecimaScheduler(
            num_executors=n,
            embed_dim=16,
            gnn_mlp_kwargs={
                "hid_dims": [32, 16],
                "act_cls": "LeakyReLU",
                "act_kwargs": {"negative_slope": 0.2},
            },
            policy_mlp_kwargs={"hid_dims": [64, 64], "act_cls": "Tanh"},
            state_dict_path=state_dict,
        )
    raise ValueError(name)


def run_episode(scheduler, seed: int = 0, render: bool = True,
                max_steps: int = 20000, live: bool = False) -> float:
    params = EnvParams(**ENV_CFG)
    bank = make_workload_bank(params.num_executors, params.max_stages)
    if bank.max_stages != params.max_stages:
        params = params.replace(
            max_stages=bank.max_stages, max_levels=bank.max_stages
        )
    state = core.reset(params, bank, jax.random.PRNGKey(seed))
    renderer = GanttRenderer(
        params.num_executors,
        live_path="screenshot.png" if live else None,
    ) if render else None
    rng = jax.random.PRNGKey(seed + 1)
    policy = jax.jit(scheduler.policy)

    steps = 0
    while not bool(state.terminated | state.truncated) and steps < max_steps:
        obs = observe(params, state)
        rng, sub = jax.random.split(rng)
        stage_idx, num_exec, _ = policy(sub, obs)
        state, _, _, _ = core.step(
            params, bank, state, jnp.int32(stage_idx), jnp.int32(num_exec)
        )
        if renderer is not None:
            renderer.record(state)
        steps += 1

    avg = float(metrics.avg_job_duration(state))
    print(f"{scheduler.name}: avg job duration = {avg * 1e-3:.1f}s "
          f"({steps} decisions)")
    if renderer is not None:
        print("saved", renderer.render("screenshot.png"))
    return avg


if __name__ == "__main__":
    from sparksched_tpu.config import enable_compilation_cache

    enable_compilation_cache()
    p = ArgumentParser()
    p.add_argument("--sched", default="fair",
                   choices=["fair", "fifo", "random", "decima"])
    p.add_argument("--state-dict", default=None,
                   help="Decima weights (.pt torch or .msgpack); "
                        "default: the shipped "
                        f"{DEFAULT_DECIMA_CKPT}")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no-render", action="store_true")
    p.add_argument("--live", action="store_true",
                   help="refresh screenshot.png during the episode "
                        "(reference render_frame analog)")
    args = p.parse_args()
    run_episode(
        make_scheduler(args.sched, args.state_dict),
        seed=args.seed,
        render=not args.no_render,
        live=args.live,
    )
