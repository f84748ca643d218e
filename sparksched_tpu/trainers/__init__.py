"""RL training layer (reference trainers/): on-device rollouts, returns,
critic-free baselines, PPO and VPG."""

from .baselines import group_baselines  # noqa: F401
from .returns import (  # noqa: F401
    AvgNumJobsBuffer,
    differential_returns,
    discounted_returns,
    step_dts,
)
from .rollout import (  # noqa: F401
    Rollout,
    StoredObs,
    collect_async,
    collect_flat_async_batch,
    collect_flat_sync_batch,
    collect_sync,
    store_obs,
    stored_to_observation,
)
from .trainer import TrainState, Trainer, make_optimizer, make_trainer  # noqa: F401,E501
from .ppo import PPO  # noqa: F401
from .vpg import VPG  # noqa: F401
