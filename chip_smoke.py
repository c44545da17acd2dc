"""Smoke run of Cobra's serving path on one TPU chip.

Drives the system through the entry points a user calls — ``CobraSession``
and ``ServingRuntime.serve`` with the compiled tier on — at the paper's
Experiment-1 scale (1,000,000 orders, 73,000 customers; data from
``--seed``), and checks every answer:

  a. JAX's first device is a TPU; there is no CPU fallback;
  b. the tables are built, and their columns live on that device;
  c. under the paper's Experiment-1 optimizer setting, P0 is served in
     two batches of 64 — the first on the interpreter, the second promoted
     to the compiled tier, whose prefetch lookups run the ``join_probe``
     kernel — and P0_COUNT (P0 that also counts its orders) one request at
     a time, where the count stays in the loop and runs the
     ``segment_reduce`` kernel (at batch 8 and above the optimizer moves
     it into a SQL ``count(*)``);
  d. every served P0 result equals a numpy evaluation of its semantics,
     ``myFunc(o_id, c_birth_year[o_customer_sk])``;
  e. at 4,000 orders / 8,000 customers, where the exact row-at-a-time
     interpreter can run (it syncs with the device per column per row),
     the compiled tier matches the interpreters in outputs and simulated
     clock — both for the optimized plans and for P0 as written, whose ORM
     navigation runs the ``join_probe`` kernel through the navigation hook;
  f. a four-worker ``ClusterRuntime`` matches one ``ServingRuntime`` on a
     W_E stream with W_A writes, in outputs and in final table contents.

It prints the device, the kernel calls per implementation (compiled
Pallas, or the reference for keys a kernel cannot take) and the wall time
of each phase — smoke timings, not benchmark results. On the TPU, a kernel
reached with no compiled call fails the run. Any failure raises and exits
non-zero; the last line of a passing run is one JSON object.

Usage::

    python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import CobraSession, OptimizerConfig  # noqa: E402
from repro.api.lift import lift_program, load_all  # noqa: E402
from repro.cluster import ClusterRuntime  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import CostCatalog  # noqa: E402
from repro.core.regions import get_function  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.programs import (ORDERS_CUSTOMER_REL, make_orders_customer_db,  # noqa: E402
                            make_p0, make_wilos_a, make_wilos_db,
                            make_wilos_e)
from repro.relational.database import SLOW_REMOTE, DatabaseServer  # noqa: E402
from repro.runtime import ServingRuntime  # noqa: E402

N_ORDERS, N_CUSTOMERS = 1_000_000, 73_000     # Experiment 1 (Sec. VIII)
SMALL_ORDERS, SMALL_CUSTOMERS = 4_000, 8_000  # where the exact tier runs
BATCH = 64
KERNELS = ("join_probe", "segment_reduce")

myFunc = get_function("myFunc")


def P0_COUNT():
    n = 0
    result = []
    for o in load_all("orders"):
        cust = o.customer
        n = n + 1
        result.append(myFunc(o.o_id, cust.c_birth_year))
    return n, result


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def programs():
    return {"P0": make_p0(),
            "P0_COUNT": lift_program(P0_COUNT,
                                     relations=[ORDERS_CUSTOMER_REL])}


def expected_p0(db) -> np.ndarray:
    """P0's result by plain numpy: each order's customer by key, then the
    program's own scalar function."""
    orders, cust = db.table("orders"), db.table("customer")
    c_sk = np.asarray(cust.column("c_customer_sk"))
    year_by_key = np.zeros(int(c_sk.max()) + 1, np.int64)
    year_by_key[c_sk] = np.asarray(cust.column("c_birth_year"))
    years = year_by_key[np.asarray(orders.column("o_customer_sk"))]
    return myFunc(np.asarray(orders.column("o_id")).astype(np.int64), years)


def check_outputs(name: str, outputs, want: np.ndarray, n_orders: int):
    got = np.asarray(outputs["result"])
    check(got.shape == want.shape and np.array_equal(got, want),
          f"{name}: served result differs from the numpy reference")
    if name == "P0_COUNT":
        check(outputs["n"] == float(n_orders),
              f"{name}: counted {outputs['n']} orders, not {n_orders}")


def phase_tables(dev, seed: int):
    db = make_orders_customer_db(N_ORDERS, N_CUSTOMERS, seed=seed)
    nbytes = 0
    for tname in ("orders", "customer"):
        t = db.table(tname)
        for c in t.schema.names:
            col = t.column(c)
            check(isinstance(col, jax.Array) and col.devices() == {dev},
                  f"{tname}.{c} is not on {dev}")
            nbytes += col.nbytes
            col.block_until_ready()
        print(f"table {tname}: {t.nrows} rows on {dev}")
    print(f"column bytes on the device: {nbytes}")
    return db


def phase_serve(db):
    """Serve each program until it is promoted: the first batch runs on
    the interpreter, the later ones on the compiled tier."""
    session = CobraSession(db, CostCatalog(SLOW_REMOTE),
                           config=OptimizerConfig.preset("paper-exp1-3"))
    want = expected_p0(db)
    calls = Counter()
    for name, batch, n_batches in (("P0", BATCH, 2), ("P0_COUNT", 1, 3)):
        rt = ServingRuntime(session, batch_size=batch,
                            compile_hot_plans=batch + 1)
        exe = rt.register(programs()[name])
        print(f"{name} plan at batch {batch}: {exe.program.body!r}"[:400])
        for _ in range(n_batches):
            for r in rt.serve([(name, {})] * batch):
                check_outputs(name, r.outputs, want, N_ORDERS)
        tel = rt.compiler.telemetry()
        check(tel["interpreted_batches"] == 1
              and tel["compiled_batches"] == n_batches - 1,
              f"{name}: expected 1 interpreted batch, then compiled: {tel}")
        calls += rt.compiler.kernel_calls()
    return calls


def phase_tiers(seed: int):
    """Compiled tier against the fast and exact interpreters, at a size the
    exact interpreter can run; the plans as optimized and as written."""
    db = make_orders_customer_db(SMALL_ORDERS, SMALL_CUSTOMERS, seed=seed)
    want = expected_p0(db)
    calls = Counter()
    configs = {"paper-exp1-3": OptimizerConfig.preset("paper-exp1-3"),
               "as-written": OptimizerConfig(rules=())}
    for label, config in configs.items():
        session = CobraSession(db, CostCatalog(SLOW_REMOTE), config=config)
        for name, program in programs().items():
            exe = session.compile(program)
            params = [{}] * 2
            fast = exe.run_batch(params, tier="interpreter")
            exact = exe.run_batch(params, mode="exact", tier="interpreter")
            comp = exe.run_batch(params, tier="compiled")
            tag = f"{label}/{name}"
            check(comp.tier == "compiled", f"{tag}: compiled tier not taken")
            check(comp.simulated_s == fast.simulated_s,
                  f"{tag}: simulated clock differs between tiers")
            for a, b, c in zip(fast.results, exact.results, comp.results):
                check(a.outputs == b.outputs == c.outputs,
                      f"{tag}: outputs differ between tiers")
                check(a.simulated_s == c.simulated_s,
                      f"{tag}: per-request simulated clock differs")
                check_outputs(tag, c.outputs, want, SMALL_ORDERS)
            calls += exe.lower().kernel_calls()
    return calls


def phase_cluster(seed: int):
    """Four workers against one runtime, W_E with W_A writes."""
    def fresh():
        src = make_wilos_db(1000, seed=seed)
        return DatabaseServer(dict(src.tables), src.model)

    reqs = []
    for i in range(30):
        reqs.append(("W_E", {"worklist": [i % 7]}))
        if i % 11 == 3:
            reqs.append(("W_A", {}))
    db1 = fresh()
    single = ServingRuntime(CobraSession(db1), batch_size=8)
    cl = ClusterRuntime(fresh(), n_workers=4,
                        partition_keys={"tasks": "t_role_id"},
                        affinity={"W_E": "worklist"}, max_batch=8)
    for mk in (make_wilos_e, make_wilos_a):
        single.register(mk())
        cl.register(mk())
    r1, r2 = single.serve(reqs), cl.serve(reqs)
    check(len(r1) == len(r2) == len(reqs), "cluster lost responses")
    for i, (a, b) in enumerate(zip(r1, r2)):
        check(a.outputs == b.outputs, f"cluster request {i} differs")
    for tname in db1.tables:
        t1, t2 = db1.table(tname), cl.db.table(tname)
        for c in t1.schema.names:
            check(np.array_equal(np.asarray(t1.column(c)),
                                 np.asarray(t2.column(c))),
                  f"cluster table {tname}.{c} differs after the writes")
    print(f"cluster: {len(reqs)} requests on 4 workers match one runtime")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} "
          f"(count {len(jax.devices())})")
    print(f"compile cache: {enable_compile_cache()}")

    timings = {}

    def timed(label, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        timings[label] = time.perf_counter() - t0
        return out

    db = timed("b_tables", phase_tables, dev, args.seed)
    serve_calls = timed("c_d_serve_full_size", phase_serve, db)
    tier_calls = timed("e_tier_parity", phase_tiers, args.seed)
    timed("f_cluster", phase_cluster, args.seed)

    for kernel in KERNELS:
        check(any(k == kernel for k, _ in serve_calls),
              f"the full-size serving phase never reached {kernel}")
    check(any(k == "join_probe" and v for (k, _), v in tier_calls.items()),
          "the tier phase never reached join_probe")
    for label, calls in (("serving at full size", serve_calls),
                         ("tier parity", tier_calls)):
        for kernel in KERNELS:
            n = {how: calls[kernel, how]
                 for how in (ops.PALLAS, ops.INTERPRET, ops.REF)}
            print(f"kernel calls ({label}) {kernel}: compiled Pallas "
                  f"{n[ops.PALLAS]}, interpret {n[ops.INTERPRET]}, "
                  f"reference {n[ops.REF]}")
            if sum(n.values()):
                check(n[ops.PALLAS] > 0,
                      f"{kernel} was reached with no compiled Pallas call")
    for label, s in timings.items():
        print(f"phase {label}: {s:.3f} s wall (smoke timing, not a "
              f"benchmark result)")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
