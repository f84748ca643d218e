"""Workload factory + data-sampler plugin boundary.

The reference exposes an overridable `DataSampler` ABC selected by the
`data_sampler_cls` config string through a globals() factory
(spark_sched_sim/data_samplers/__init__.py:9-15,
data_samplers/data_sampler.py:9-23). The TPU-native equivalent of "a
sampler object consulted inside the event loop" is a *template provider*:
a callable that produces host-side template dicts (DAG structure +
per-(stage, wave, executor-level) duration buckets) which `pack_bank`
turns into fixed-shape device arrays. Custom workloads plug in by
registering a provider under a name and selecting it by config string —
no package edits required.
"""

import os.path as osp
from typing import Any, Callable, Protocol

from .bank import (  # noqa: F401
    EXEC_LEVEL_VALUES,
    NUM_EXEC_LEVELS,
    WorkloadBank,
    bank_depth,
    bank_dtype_label,
    load_tpch_templates,
    pack_bank,
    quantize_bank,
)
from .synthetic import make_templates  # noqa: F401


class TemplateProvider(Protocol):
    """Plugin contract (replaces the reference DataSampler ABC,
    data_sampler.py:9-23): return a list of template dicts, each with
    `adj` (bool [s,s] parent->child), `num_tasks` (int [s]), and
    `durations` ({stage: {wave_name: {exec_level: list[float]}}})."""

    def __call__(
        self,
        *,
        num_executors: int,
        max_stages: int,
        bucket_size: int,
        data_dir: str,
        seed: int,
    ) -> list[dict[str, Any]]: ...


def _tpch_provider(
    *,
    num_executors: int,
    max_stages: int,
    bucket_size: int,
    data_dir: str,
    seed: int,
) -> list[dict[str, Any]]:
    """Default provider: real TPC-H traces when present on disk (the
    reference auto-downloads them, tpch.py:109-115 — impossible without
    egress), else the synthetic TPC-H-like bank."""
    if osp.isdir(data_dir):
        return load_tpch_templates(data_dir)
    return make_templates(seed=seed, bucket_size=bucket_size)


_DATA_SAMPLERS: dict[str, Callable[..., list[dict[str, Any]]]] = {
    # reference class name, for drop-in config compatibility
    "TPCHDataSampler": _tpch_provider,
}


def register_data_sampler(
    name: str, provider: Callable[..., list[dict[str, Any]]]
) -> None:
    """Register a custom workload provider selectable via the
    `data_sampler_cls` config string."""
    _DATA_SAMPLERS[name] = provider


def make_workload_bank(
    num_executors: int,
    max_stages: int = 20,
    bucket_size: int = 16,
    data_dir: str = "data/tpch",
    seed: int = 2024,
    data_sampler_cls: str | None = None,
    bank_dtype: str | None = None,
    **_: object,
) -> WorkloadBank:
    """Factory mirroring the reference `make_data_sampler`
    (spark_sched_sim/data_samplers/__init__.py:9-15): dispatches on the
    `data_sampler_cls` config string through the provider registry.
    `bank_dtype` (ISSUE 7; an `env:` config key — "int16", "int8" or
    "bf16", default f32) selects the low-precision duration-table
    layout via `quantize_bank`."""
    name = data_sampler_cls or "TPCHDataSampler"
    if name not in _DATA_SAMPLERS:
        raise ValueError(
            f"'{name}' is not a registered data sampler "
            f"(have: {sorted(_DATA_SAMPLERS)})"
        )
    templates = _DATA_SAMPLERS[name](
        num_executors=num_executors,
        max_stages=max_stages,
        bucket_size=bucket_size,
        data_dir=data_dir,
        seed=seed,
    )
    max_stages = max(
        max_stages, max(t["adj"].shape[0] for t in templates)
    )
    bank = pack_bank(templates, num_executors, max_stages, bucket_size)
    if bank_dtype is not None:
        bank = quantize_bank(bank, bank_dtype)
    return bank


# drop-in alias for the reference factory name
# (spark_sched_sim/data_samplers/__init__.py:9-15)
make_data_sampler = make_workload_bank
