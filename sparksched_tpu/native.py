"""ctypes binding for the native host engine (native/sparksched_core.cpp).

The C++ engine is the framework's host runtime: a fast single-env
discrete-event simulator with the exact semantics of the vectorized XLA
core, used as a CPU fallback, as an independent cross-check of the TPU
program, and for single-episode tooling. Built lazily with g++ (no
pybind11 dependency — plain C ABI)."""

from __future__ import annotations

import ctypes as ct
import os
import os.path as osp
import subprocess

import numpy as np

from .config import EnvParams
from .workload.bank import EXEC_LEVEL_VALUES, WorkloadBank

_SRC = osp.join(osp.dirname(osp.dirname(osp.abspath(__file__))),
                "native", "sparksched_core.cpp")
_LIB = None


def _build_lib() -> str:
    """The library is a build output (git-ignored): built from the
    committed source on first use in a fresh checkout, and again when
    the source is newer. Written under a temporary name and renamed, so
    concurrent first users never load a half-written file."""
    out = osp.join(osp.dirname(_SRC), "libsparksched.so")
    if not osp.isfile(out) or os.path.getmtime(out) < os.path.getmtime(_SRC):
        tmp = f"{out}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True,
        )
        os.replace(tmp, out)
    return out


def _lib() -> ct.CDLL:
    global _LIB
    if _LIB is None:
        lib = ct.CDLL(_build_lib())
        lib.ss_create.restype = ct.c_void_p
        lib.ss_create.argtypes = [
            ct.POINTER(ct.c_int32), ct.POINTER(ct.c_double),
            ct.c_int32, ct.c_int32, ct.c_int32, ct.c_int32,
            ct.POINTER(ct.c_int32), ct.POINTER(ct.c_int32),
            ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_float),
            ct.POINTER(ct.c_int32), ct.POINTER(ct.c_int32),
            ct.POINTER(ct.c_float),
        ]
        lib.ss_destroy.argtypes = [ct.c_void_p]
        lib.ss_reset.argtypes = [
            ct.c_void_p, ct.POINTER(ct.c_double), ct.POINTER(ct.c_int32),
            ct.c_int32,
        ]
        lib.ss_step.restype = ct.c_double
        lib.ss_step.argtypes = [
            ct.c_void_p, ct.c_int32, ct.c_int32, ct.POINTER(ct.c_int32)
        ]
        lib.ss_wall_time.restype = ct.c_double
        lib.ss_wall_time.argtypes = [ct.c_void_p]
        lib.ss_observe.argtypes = [
            ct.c_void_p, ct.POINTER(ct.c_int32), ct.POINTER(ct.c_float),
            ct.POINTER(ct.c_uint8), ct.POINTER(ct.c_uint8),
            ct.POINTER(ct.c_int32), ct.POINTER(ct.c_int32),
            ct.POINTER(ct.c_int32), ct.POINTER(ct.c_uint8),
            ct.POINTER(ct.c_uint8),
        ]
        lib.ss_job_durations.restype = ct.c_int32
        lib.ss_job_durations.argtypes = [ct.c_void_p,
                                         ct.POINTER(ct.c_double)]
        _LIB = lib
    return _LIB


def _ptr(a: np.ndarray, dtype):
    return a.ctypes.data_as(ct.POINTER(dtype))


class NativeEnv:
    """Single-environment host engine with the `core.py` step contract
    (flat padded stage index, 1-based num_exec)."""

    def __init__(self, params: EnvParams, bank: WorkloadBank,
                 seed: int = 0) -> None:
        self.params = params
        lib = _lib()
        num_stages = np.ascontiguousarray(bank.num_stages, np.int32)
        num_tasks = np.ascontiguousarray(bank.num_tasks, np.int32)
        adj = np.ascontiguousarray(np.asarray(bank.adj), np.uint8)
        dur = np.ascontiguousarray(bank.dur, np.float32)
        cnt = np.ascontiguousarray(bank.cnt, np.int32)
        rough = np.ascontiguousarray(bank.rough_duration, np.float32)
        levels = np.ascontiguousarray(EXEC_LEVEL_VALUES, np.int32)
        t, s = num_tasks.shape
        _, _, _, L, K = dur.shape
        iparams = np.array(
            [params.num_executors, params.max_jobs, seed], np.int32
        )
        dparams = np.array(
            [params.moving_delay, params.warmup_delay], np.float64
        )
        assert s == params.max_stages, (s, params.max_stages)
        self._h = lib.ss_create(
            _ptr(iparams, ct.c_int32), _ptr(dparams, ct.c_double),
            t, s, L, K,
            _ptr(num_stages, ct.c_int32), _ptr(num_tasks, ct.c_int32),
            _ptr(adj, ct.c_uint8), _ptr(dur, ct.c_float),
            _ptr(cnt, ct.c_int32), _ptr(levels, ct.c_int32),
            _ptr(rough, ct.c_float),
        )
        self._lib = lib
        self.terminated = False

    def __del__(self) -> None:
        if getattr(self, "_h", None):
            self._lib.ss_destroy(self._h)
            self._h = None

    def reset(self, arrivals: np.ndarray, templates: np.ndarray) -> None:
        arrivals = np.ascontiguousarray(arrivals, np.float64)
        templates = np.ascontiguousarray(templates, np.int32)
        self._lib.ss_reset(
            self._h, _ptr(arrivals, ct.c_double),
            _ptr(templates, ct.c_int32), len(arrivals),
        )
        self.terminated = False

    def step(self, stage_idx: int, num_exec: int) -> tuple[float, bool]:
        term = ct.c_int32(0)
        r = self._lib.ss_step(self._h, int(stage_idx), int(num_exec),
                              ct.byref(term))
        self.terminated = bool(term.value)
        return float(r), self.terminated

    @property
    def wall_time(self) -> float:
        return float(self._lib.ss_wall_time(self._h))

    def observe(self) -> dict[str, np.ndarray]:
        p = self.params
        js = p.max_jobs * p.max_stages
        remaining = np.zeros(js, np.int32)
        duration = np.zeros(js, np.float32)
        schedulable = np.zeros(js, np.uint8)
        frontier = np.zeros(js, np.uint8)
        supplies = np.zeros(p.max_jobs, np.int32)
        job_mask = np.zeros(p.max_jobs, np.uint8)
        node_mask = np.zeros(js, np.uint8)
        committable = ct.c_int32(0)
        source_job = ct.c_int32(0)
        self._lib.ss_observe(
            self._h, _ptr(remaining, ct.c_int32), _ptr(duration, ct.c_float),
            _ptr(schedulable, ct.c_uint8), _ptr(frontier, ct.c_uint8),
            _ptr(supplies, ct.c_int32), ct.byref(committable),
            ct.byref(source_job), _ptr(job_mask, ct.c_uint8),
            _ptr(node_mask, ct.c_uint8),
        )
        shape = (p.max_jobs, p.max_stages)
        return {
            "remaining": remaining.reshape(shape),
            "duration": duration.reshape(shape),
            "schedulable": schedulable.reshape(shape).astype(bool),
            "frontier": frontier.reshape(shape).astype(bool),
            "exec_supplies": supplies,
            "job_mask": job_mask.astype(bool),
            "node_mask": node_mask.reshape(shape).astype(bool),
            "num_committable": int(committable.value),
            "source_job": int(source_job.value),
        }

    def job_durations(self) -> np.ndarray:
        out = np.zeros(self.params.max_jobs, np.float64)
        n = self._lib.ss_job_durations(self._h, _ptr(out, ct.c_double))
        return out[:n]
