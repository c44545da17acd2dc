"""Data and plain reference of ``wilos-tasks-1m``. Imports nothing of the
program under test.

``generate`` draws the rows of ``repro.programs.make_wilos_db`` (same
columns, ranges and order of draws) from the benchmark's seed, with one
change: the 10:1 mapping of tasks to roles is exact (each role has 10
tasks, in seeded order) where ``make_wilos_db`` draws each task's role
uniformly.
``reference`` is W_E by plain numpy: for each worklist key in order, the
hours of its tasks in table order. ``control`` is the same with the hours
in bfloat16, the precision below the float32 the deployment stores.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def generate(sizes: dict, rng: np.random.Generator) -> dict:
    n_tasks, n_roles = int(sizes["n_tasks"]), int(sizes["n_roles"])
    roles = {
        "r_id": np.arange(n_roles, dtype=np.int64),
        "r_rank": rng.integers(0, 5, n_roles),   # 20% selectivity on one rank
        "r_payload": rng.integers(0, 1 << 20, n_roles),
    }
    tasks = {
        "t_id": np.arange(n_tasks, dtype=np.int64),
        # the 10:1 mapping held exactly: every role has n_tasks // n_roles
        # tasks, so a worklist's rows, and a run's work, do not vary
        # with the seed
        "t_role_id": rng.permutation(
            np.resize(np.arange(n_roles, dtype=np.int64), n_tasks)),
        "t_state": rng.integers(0, 5, n_tasks),
        "t_hours": rng.uniform(0, 40, n_tasks).astype(np.float32),
        "t_payload": rng.integers(0, 1 << 20, n_tasks),
    }
    return {"roles": roles, "tasks": tasks}


def _index(columns: dict):
    idx = columns.get("_by_role")
    if idx is None:
        role = columns["tasks"]["t_role_id"]
        order = np.argsort(role, kind="stable")
        idx = columns["_by_role"] = (role[order], order)
    return idx


def _select(columns: dict, params: dict, hours: np.ndarray) -> np.ndarray:
    keys, order = _index(columns)
    parts = []
    for wid in params.get("worklist", ()):
        lo = np.searchsorted(keys, wid, side="left")
        hi = np.searchsorted(keys, wid, side="right")
        parts.append(hours[order[lo:hi]])
    return np.concatenate(parts) if parts else np.zeros(0, hours.dtype)


def reference(columns: dict, program: str, params: dict) -> np.ndarray:
    if program != "W_E":
        raise KeyError(program)
    return _select(columns, params,
                   columns["tasks"]["t_hours"].astype(np.float64))


def control(columns: dict, program: str, params: dict) -> np.ndarray:
    if program != "W_E":
        raise KeyError(program)
    hours = columns["tasks"]["t_hours"].astype(ml_dtypes.bfloat16)
    return _select(columns, params, hours.astype(np.float64))


def answer(outputs: dict) -> np.ndarray:
    """The served answer as a vector comparable with ``reference``."""
    return np.asarray(outputs["result"], dtype=np.float64)
