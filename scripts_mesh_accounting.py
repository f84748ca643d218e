"""Mesh-scaling accounting on the virtual CPU mesh.

One real chip is available, so wall-clock scaling cannot be measured;
what CAN be measured without hardware is how the compiled SPMD programs
partition work. For dp in {1, 2, 4, 8} this script compiles the PPO
collect (the trainer's collector, which ISSUE 6 ships sharded) and
update at fixed GLOBAL batch (lanes sharded over the mesh,
params replicated — parallel.py) and records, per program:

- the per-device shard shape of the rollout buffer's largest field
  (collect out_sharding),
- XLA cost_analysis FLOPs — for an SPMD program this is per-device work,
  so near-1/dp scaling is the scaling claim made concrete,
- the collective ops in the optimized HLO of the update (all-reduce for
  gradient/advantage reductions and their re-associations) and their
  count — the ICI/DCN traffic the design pays. The census helpers live
  in parallel.py and are shared with tests/test_parallel.py's census
  test, so the script and the CI pin cannot drift on what counts as a
  collective.

Writes the table to stdout and appends a dated section to PERF_ROUNDS.md when
run with --record. CPU-only;
never touches the chip (force_virtual_cpu_devices before any jax call).
"""

import sys

sys.path.insert(0, "/root/repo")
from __graft_entry__ import force_virtual_cpu_devices  # noqa: E402

force_virtual_cpu_devices(8)

import jax  # noqa: E402

from sparksched_tpu.parallel import (  # noqa: E402
    collective_census,
    compiled_flops,
    make_mesh,
)
from sparksched_tpu.trainers.ppo import PPO  # noqa: E402

AGENT = {
    "agent_cls": "DecimaScheduler", "embed_dim": 16,
    "gnn_mlp_kwargs": {"hid_dims": [32, 16], "act_cls": "LeakyReLU",
                       "act_kwargs": {"negative_slope": 0.2}},
    "policy_mlp_kwargs": {"hid_dims": [64, 64], "act_cls": "Tanh"},
}
ENV = {
    "num_executors": 10, "job_arrival_cap": 8, "moving_delay": 2000.0,
    "job_arrival_rate": 4.0e-5, "warmup_delay": 1000.0,
}
TRAIN = {
    "trainer_cls": "PPO", "num_iterations": 1, "num_sequences": 2,
    "num_rollouts": 8, "seed": 0, "artifacts_dir": "/tmp/mesh_acct",
    "use_tensorboard": False, "num_epochs": 1, "num_batches": 4,
    "clip_range": 0.2, "target_kl": 0.01, "entropy_coeff": 0.04,
    "beta_discount": 5.0e-3, "opt_kwargs": {"lr": 3.0e-4},
    "max_grad_norm": 0.5, "rollout_steps": 48,
}

def sweep() -> list[dict]:
    rows = []
    for dp in (1, 2, 4, 8):
        t = PPO(AGENT, ENV, TRAIN, mesh=make_mesh(dp))
        state = t.init_state()

        lowered_c = t._collect_jit.lower(
            state.params, state.iteration, state.rng, None
        )
        comp_c = lowered_c.compile()
        # execute through the AOT-compiled object (a fresh
        # t._collect_jit call would re-trace and recompile)
        ro, _, _ = comp_c(state.params, state.iteration, state.rng, None)
        shard_shape = ro.obs.duration.sharding.shard_shape(
            ro.obs.duration.shape
        )

        lowered_u = t._update_jit.lower(state, ro)
        comp_u = lowered_u.compile()

        rows.append({
            "dp": dp,
            "global_lanes": t.num_envs,
            "lane_shard": shard_shape[0],
            "obs_shard_shape": "x".join(map(str, shard_shape)),
            "collect_gflops": compiled_flops(comp_c) / 1e9,
            "update_gflops": compiled_flops(comp_u) / 1e9,
            "update_collectives": collective_census(comp_u.as_text()),
        })
        print(rows[-1], flush=True)
    return rows


def main() -> None:
    rows = sweep()
    base_c, base_u = rows[0]["collect_gflops"], rows[0]["update_gflops"]
    lines = [
        "",
        "## Mesh scaling accounting (virtual CPU mesh, "
        "scripts_mesh_accounting.py)",
        "",
        "Fixed global batch (16 lanes x 48 steps, 8-job envs), lanes "
        "sharded over a 1-D dp mesh, params replicated, the trainer's "
        "collector and update. XLA `cost_analysis` "
        "FLOPs are per-device for SPMD programs; the table shows "
        "per-device work dropping ~1/dp while the update pays only the "
        "reduction-family collectives (gradient psum + advantage "
        "normalization; the shard-aligned fold_in minibatch keys keep "
        "resharding families out — tests/test_parallel.py pins this).",
        "",
        "| dp | lanes/device | obs shard [B,T,F] | collect "
        "GFLOP/dev (x of dp=1) | update GFLOP/dev (x of dp=1) | update "
        "collectives |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        colls = ", ".join(
            f"{k}:{v}" for k, v in sorted(r["update_collectives"].items())
        ) or "none"
        lines.append(
            f"| {r['dp']} | {r['lane_shard']} "
            f"| {r['obs_shard_shape']} "
            f"| {r['collect_gflops']:.2f} "
            f"({r['collect_gflops'] / base_c:.2f}x) "
            f"| {r['update_gflops']:.2f} "
            f"({r['update_gflops'] / base_u:.2f}x) | {colls} |"
        )
    out = "\n".join(lines) + "\n"
    print(out)
    if "--record" in sys.argv:
        with open("PERF_ROUNDS.md", "a") as fp:
            fp.write(out)
        print("appended to PERF_ROUNDS.md")


if __name__ == "__main__":
    main()
