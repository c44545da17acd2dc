"""Compiled-tier execution: kernel-backed hooks + the splicing interpreter.

A :class:`~repro.compiled.lower.CompiledLoop` executes through
:func:`repro.core.vectorize.exec_loop_plan` — the same statement walk the
fast interpreter uses, which owns ALL simulated-time charging — but with
:class:`~repro.core.vectorize.LoopHooks` that move the data differently:

  * **navigation / cache-lookup probes** run against the build side's
    sorted keys (:class:`_BuildKeys`), probed by the ``join_probe`` kernel
    as ``kernels.ops`` dispatches it for the platform on the ``"kernels"``
    backend, or by a host search on the ``"numpy"`` backend and for keys
    the kernel cannot take. Every probe is counted under the
    implementation that ran (``CompiledLoop.kernel_calls``). The
    navigation index (:class:`_ProbeIndex`) is keyed by the SAME (stats
    version, data version, instance) epoch the
    serving :class:`~repro.runtime.sitecache.SiteCache` uses, so an
    ``analyze()`` or a write landing mid-stream rebuilds it instead of
    serving stale gathers — compiled results stay bit-identical to
    interpreted ones under concurrent stats/data movement;
  * **accumulator folds** go through ``segment_reduce`` only for the
    accumulators lowering proved fold-safe AND whose runtime values pass
    the exactness gate (integer deltas within fp32's exact range);
    everything else takes the default float64 sequential-equivalent path.

The :class:`SplicingInterpreter` is the tiered fallback: a plain
:class:`~repro.core.regions.Interpreter` that, on reaching a loop bound by
the lowering, executes the compiled segment and, everywhere else (``while``
guards, early-exit loops, update-carrying bodies, non-table or empty
sources at run time), defers to the exact row-at-a-time semantics.
"""

from __future__ import annotations

from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

from ..core.regions import Interpreter, IVar, LoopRegion
from ..core.vectorize import (LoopHooks, _broadcast, _eval_vec,
                              _vec_accumulate, exec_loop_plan)
from ..kernels import ops
from ..kernels import ref as kref
from ..kernels.join_probe import build_direct_table, join_probe
from ..obs.trace import NOOP_TRACER
from ..obs.transfer import to_device, to_host
from ..relational.memo import TableMemo
from ..relational.table import Table

__all__ = ["SplicingInterpreter", "make_hooks"]

# fp32 holds integers exactly up to 2**24: the kernel fold (which
# accumulates in float32) is only taken below this bound
_EXACT_FP32 = float(1 << 24)

# bounded memos: a serving process sees unbounded distinct query-result
# tables; the hooks only ever pin this many
_ROW_SOURCE_CAP = 32
_PROBE_INDEX_CAP = 64


def _columns(t: Table):
    return {c: to_host(t.column(c), "compiled.columns")
            for c in t.schema.names}


class _BuildKeys:
    """A build side's key column in sorted order — ``order`` is its stable
    sort and ``sorted`` the keys in that order — plus, once the kernel
    probes it, its direct-address table on the device."""

    __slots__ = ("order", "sorted", "space", "direct")

    def __init__(self, t: Table, col: str):
        keys = to_host(t.column(col), "compiled.build_keys")
        self.order = np.argsort(keys, kind="stable")
        self.sorted = keys[self.order]
        self.space = ops.direct_key_space(self.sorted)
        self.direct = None


class _ProbeIndex:
    """Per-(table, key column) probe state, rebuilt when the epoch moves."""

    __slots__ = ("epoch", "table", "keys", "cols")

    def __init__(self, epoch, t: Table, key_col: str):
        self.epoch = epoch
        self.table = t
        self.keys = _BuildKeys(t, key_col)
        self.cols = _columns(t)


class _ProbeIndexCache:
    def __init__(self, owner, cap: int = _PROBE_INDEX_CAP):
        self.owner = owner          # CompiledLoop (telemetry)
        self._memo: "OrderedDict[tuple, _ProbeIndex]" = OrderedDict()
        self.cap = cap

    def get(self, env, table_name: str, key_col: str) -> _ProbeIndex:
        epoch = (env.db.instance_token,) + tuple(
            env.db.site_epoch((table_name,)))
        k = (table_name, key_col)
        idx = self._memo.get(k)
        if idx is not None and idx.epoch == epoch:
            self._memo.move_to_end(k)
            return idx
        idx = _ProbeIndex(epoch, env.db.table(table_name), key_col)
        self._memo[k] = idx
        self.owner.index_rebuilds += 1
        while len(self._memo) > self.cap:
            self._memo.popitem(last=False)
        return idx


def _probe_impl(cl, bk: _BuildKeys, keys: np.ndarray) -> str:
    """The implementation :func:`_probe` runs for these keys."""
    if cl.backend == "kernels" and np.issubdtype(keys.dtype, np.integer):
        return ops.probe_impl(bk.space)
    return ops.REF


def _probe(cl, bk: _BuildKeys, keys: np.ndarray) -> np.ndarray:
    """Build-side row (an entry of ``bk.order``) for each key, -1 on miss.

    The ``"kernels"`` backend probes with ``join_probe`` where
    ``kernels.ops`` dispatches it (compiled on the TPU), against a
    direct-address table built once per build side. Build keys the kernel
    cannot take (``ops.direct_key_space``), probe keys that are not
    integers, other platforms and the ``"numpy"`` backend search the sorted
    keys on the host instead — the same values. Either way the call is
    counted under the implementation that ran."""
    how = _probe_impl(cl, bk, keys)
    cl.kernel_calls["join_probe", how] += 1
    if bk.sorted.shape[0] == 0:
        return np.full(keys.shape, -1, np.int32)
    if how != ops.REF:
        if bk.direct is None:
            bk.direct = build_direct_table(
                to_device(bk.sorted, jnp.int32, "compiled.probe"), bk.space)
        # out-of-range keys miss; clamp them before narrowing to int32
        keys = np.where((keys >= 0) & (keys < bk.space), keys, -1)
        pos = to_host(join_probe(to_device(keys, jnp.int32, "compiled.probe"),
                                 bk.direct, interpret=how == ops.INTERPRET),
                      "compiled.probe")
    else:
        pos = np.clip(np.searchsorted(bk.sorted, keys), 0,
                      bk.sorted.shape[0] - 1)
        pos = np.where(bk.sorted[pos] == keys, pos, -1)
    return np.where(pos >= 0, bk.order[pos], -1).astype(np.int32)


def make_hooks(cl) -> LoopHooks:
    """Bind kernel-backed hooks for one :class:`CompiledLoop`.

    Every hook is observationally identical to the vectorize defaults —
    same values, same ORM-cache mutations, same failure behavior — only
    the gather/fold machinery differs (epoch-cached indices + kernels)."""
    probe_cache = _ProbeIndexCache(cl)
    row_source = TableMemo(_columns, _ROW_SOURCE_CAP)
    # the build keys of a prefetch cache, by its table: each invocation
    # re-prefetches, but the same Table (the same sort as the cache's own),
    # so the direct-address table is built once and not per invocation
    prefetch_keys = TableMemo(_BuildKeys, _PROBE_INDEX_CAP)

    def probe(env, bk, keys):
        tracer = getattr(env, "tracer", NOOP_TRACER)
        with tracer.span("compiled.probe", n=keys.shape[0],
                         impl=_probe_impl(cl, bk, keys)):
            return _probe(cl, bk, keys)

    # ------------------------------------------------------------------ nav
    def nav(env, ce, target, e, n):
        base = ce.rows[e.base.name]
        keys = np.asarray(base[e.fk_field])
        idx = probe_cache.get(env, e.target, e.target_key)
        gidx = probe(env, idx.keys, keys)
        if (gidx < 0).any():
            raise KeyError(f"navigation {e!r}: missing keys (FK violation)")
        ce.rows[target] = {c: idx.cols[c][gidx] for c in idx.table.schema.names}
        # ORM cache accounting — identical to core.vectorize._vec_nav:
        # first occurrence of an uncached key = point query, every other
        # occurrence = cache hit (1 statement)
        t = idx.table
        uniq = np.unique(keys)
        new_keys = [k for k in uniq.tolist()
                    if (e.target, k) not in env._orm_cache]
        n_misses = len(new_keys)
        env.charge_statement(n - n_misses)
        m = env.db.model
        bulk = getattr(env, "bulk_nav_charge", None)
        if bulk is not None and n_misses:
            bulk(t, n_misses)
        else:
            for _ in range(n_misses):
                env._charge_query(
                    1, t.row_bytes,
                    m.startup_s + m.index_lookup_s,
                    m.startup_s + m.index_lookup_s + 1 / m.emit_rows_per_s)
        if env.orm_cache_enabled and n_misses:
            pos = np.searchsorted(idx.keys.sorted, np.asarray(new_keys))
            rows_idx = idx.keys.order[pos]
            for k, i in zip(new_keys, rows_idx.tolist()):
                env._orm_cache[(e.target, k)] = t.row(int(i))

    # --------------------------------------------------------- cache_lookup
    def cache_lookup(env, ce, target, e, n):
        entry = env._prefetch_cache.get((e.table, e.col))
        if entry is None:
            raise KeyError(f"no prefetch cache for ({e.table}, {e.col})")
        keys = _broadcast(_eval_vec(e.keyexpr, ce), n)
        t = entry["table"]
        gidx = probe(env, prefetch_keys(t, e.col), np.asarray(keys))
        if (gidx < 0).any():
            raise KeyError(f"cache lookup {e!r}: missing keys")
        cols = row_source(t)
        ce.rows[target] = {c: cols[c][gidx] for c in t.schema.names}

    # ----------------------------------------------------------- accumulate
    def accumulate(ce, stmt, e, mask, state):
        acc = stmt.target
        # a kernel-foldable acc has exactly one defining update and is never
        # read elsewhere in the body (lowering proved this), so it can have
        # no running column yet; its initial value lives in `state`
        if acc in cl.kernel_fold_accs and e.op == "+" and acc not in ce.cols:
            l_is_acc = isinstance(e.left, IVar) and e.left.name == acc
            other = e.right if l_is_acc else e.left
            delta = _broadcast(_eval_vec(other, ce), ce.n).astype(np.float64)
            if mask is not None:
                delta = np.where(mask, delta, 0.0)
            # exactness gate: the kernel accumulates in fp32, so it is only
            # taken for integer deltas whose running total stays within
            # fp32's exact integer range; then `a0 + total` is the same
            # single float64 add the cumsum path performs on its last
            # element — bit-identical. Anything else takes the default
            # sequential-equivalent float64 path.
            if np.all(delta == np.floor(delta)) \
                    and float(np.abs(delta).sum()) < _EXACT_FP32:
                total = _fold_sum(cl, delta)
                # the interpreted tier exports col[-1].item() — a float
                state[acc] = float(state.get(acc, 0.0)) + total
                return
        _vec_accumulate(ce, stmt, e, mask, state)

    return LoopHooks(nav=nav, cache_lookup=cache_lookup,
                     accumulate=accumulate, row_source=row_source)


def _fold_sum(cl, delta: np.ndarray) -> float:
    """Total of ``delta`` via the segment-reduce kernel (one segment) as
    ``kernels.ops`` dispatches it, or its numpy twin on the ``"numpy"``
    backend; counted under the implementation that ran."""
    segs = np.zeros(delta.shape[0], np.int32)
    if cl.backend == "kernels":
        cl.kernel_calls["segment_reduce", ops.impl()] += 1
        out = ops.segment_reduce(
            to_device(delta, jnp.float32, "compiled.fold_sum"),
            to_device(segs, None, "compiled.fold_sum"), 1, op="sum")
        return float(to_host(out, "compiled.fold_sum")[0])
    cl.kernel_calls["segment_reduce", ops.REF] += 1
    return float(kref.segment_reduce_np(delta, segs, 1, op="sum")[0])


class SplicingInterpreter(Interpreter):
    """Interpreter that splices compiled columnar segments into the walk.

    Loops the lowering bound execute through
    :func:`~repro.core.vectorize.exec_loop_plan` with the compiled hooks;
    every other region — and any bound loop whose run-time source is not a
    non-empty Table — takes the inherited exact path. ``mode`` governs only
    the UNBOUND loops (default ``"fast"``, like the interpreted tier), so
    the two tiers stay clock-identical statement for statement."""

    def __init__(self, env, lowered, mode: str = "fast"):
        super().__init__(env, mode)
        self.lowered = lowered

    def exec_region(self, r, state) -> None:
        if isinstance(r, LoopRegion):
            cl = self.lowered.loop_for(r)
            if cl is not None:
                src = self.eval(r.source, state)
                if isinstance(src, Table) and src.nrows > 0:
                    env = self.env
                    tracer = getattr(env, "tracer", NOOP_TRACER)
                    with tracer.span("compiled.loop",
                                     sim_clock=lambda: env.clock,
                                     loop_var=r.var, rows=src.nrows):
                        exec_loop_plan(env, r, src, state, cl.plan,
                                       hooks=cl.hooks)
                    cl.executions += 1
                    self.lowered.columnar_execs += 1
                    return
                # run-time fallback (empty or non-table source): the exact
                # path also records collection-loop iteration observations
                self.lowered.fallback_execs += 1
                self._exec_loop_exact(r, src, state)
                return
        super().exec_region(r, state)
