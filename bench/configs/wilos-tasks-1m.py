"""Deployment ``wilos-tasks-1m``: the tables and program W_E of the paper's
Experiment 4 (Wilos pattern E), built for the program under test.

The data comes from ``wilos-tasks-1m.reference.py`` (``generate``).
Schemas, row widths and the source of W_E are copied from
``repro.programs`` (``make_wilos_db``, ``make_wilos_e``).
"""

from __future__ import annotations

from repro.api.builder import col, param, q
from repro.api.lift import lift_program
from repro.relational.database import DatabaseServer
from repro.relational.table import Field, Schema, Table


def build_db(columns: dict) -> DatabaseServer:
    """Two relations with a many-to-one key (10:1): roles 132 B, tasks
    100 B on the wire; the columns go to JAX's default device."""
    r, t = columns["roles"], columns["tasks"]
    roles = Table.from_columns(
        "roles",
        Schema.of(Field("r_id", "int64", 8), Field("r_rank", "int32", 4),
                  Field("r_payload", "int32", 120)),
        **r)
    tasks = Table.from_columns(
        "tasks",
        Schema.of(Field("t_id", "int64", 8), Field("t_role_id", "int64", 8),
                  Field("t_state", "int32", 4), Field("t_hours", "float32", 4),
                  Field("t_payload", "int32", 76)),
        **t)
    return DatabaseServer({"roles": roles, "tasks": tasks})


def programs() -> list:
    """E: the same relation filtered differently across calls, as a loop
    over a worklist issuing per-key selections."""
    def W_E(worklist=()):
        result = []
        for wid in worklist:
            per_key = q("tasks").where(col("t_role_id")
                                       .eq(param("rid"))).bind(rid=wid)
            for y in per_key:
                result.append(y.t_hours)
        return result

    return [lift_program(W_E)]
