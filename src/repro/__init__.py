"""COBRA on TPU: cost-based rewriting of database applications (Emani &
Sudarshan, 2018) — a database-application optimizer and the serving
runtime built around it, over an in-process database whose columns are
JAX arrays.

The public surface is the session API::

    from repro.api import CobraSession, OptimizerConfig, ProgramBuilder, q
    from repro.core import CostCatalog

    session = CobraSession(db, CostCatalog(SLOW_REMOTE),
                           config=OptimizerConfig.preset("paper-exp1-3"))
    exe = session.compile(program)     # memo search once, plan cached
    out = exe.run()                    # execute-many (outputs + sim clock)

Programs are written with the tracing ``ProgramBuilder`` (``with``-scoped
loops, ``q()`` relational query handles, attribute/relationship navigation)
or the ``session.trace()`` decorator. Compiled plans live in a cache keyed
by (program fingerprint, cost catalog, optimizer config, per-table stats
versions of the tables the program touches); ``db.analyze(table, ...)``
bumps those versions, invalidating exactly the plans whose cost estimates
went stale.

For production-shaped workloads, ``repro.runtime`` adds batched execution
(``Executable.run_batch`` — one server round trip per query site per
batch), a disk-backed cross-session ``PlanStore``, and a feedback-driven
serving loop (``ServingRuntime``) that re-optimizes programs when observed
cardinalities drift from the estimates their plans were costed on.

Migration note: the legacy free function ``repro.core.optimize(program, db,
catalog, choice, rules)`` remains supported as a thin shim that opens a
throwaway session per call — correct, but it re-runs the full memo search
every time; hold a ``CobraSession`` for compile-once/execute-many.

  repro.api         — CobraSession, OptimizerConfig, ProgramBuilder, PlanCache
  repro.runtime     — serving: run_batch, PlanStore, feedback re-optimization
  repro.core        — the paper: regions, F-IR, Region DAG, rules, search
  repro.compiled    — the compiled execution tier (columnar loop lowering)
  repro.relational  — columnar JAX tables + simulated DB environment
  repro.kernels     — Pallas TPU kernels for the relational operators
                      (+ jnp oracles)
  repro.cluster     — sharded serving over partitioned tables
  repro.stats       — histograms, selectivity, q-error tracking
  repro.obs         — tracer spans, metrics, explain and triage
"""

__version__ = "1.2.0"
