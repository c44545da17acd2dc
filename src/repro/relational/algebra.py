"""Relational algebra over columnar JAX tables.

Query trees are what Cobra's F-IR relational leaves (σ, π, ⋈, γ — Fig. 11)
denote. Every node can:

  * ``execute(db)``   — produce a concrete ``Table`` (vectorized jnp compute)
  * ``sql()``         — render as SQL text (for logs / EXPERIMENTS.md)
  * structural hash / equality — required by the Region DAG's duplicate
    detection (Volcano/Cascades memoization).

Scalar expressions (``Col``, ``Lit``, arithmetic, comparisons, boolean
combinators, ``Func``) evaluate column-vectorized over a table.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import MetricsRegistry
from ..obs.transfer import to_device, to_host
from .memo import TableMemo
from .table import Field, Schema, Table

__all__ = [
    "Scalar", "Col", "Lit", "Arith", "Cmp", "BoolOp", "Not", "Func", "Param",
    "Query", "Scan", "Select", "Project", "Join", "SemiJoin", "Aggregate",
    "OrderBy", "Limit", "AggSpec", "equi_join_indices", "register_scalar_func",
    "scan_tables", "query_has_params", "SERVER",
]

# Process-wide counters of existential checks and of the semi-join:
# ``exists_set`` (checks a semi-join answers set-at-a-time: its probe rows),
# ``exists_per_row`` (checks the interpreter answers with a query of their
# own, ``core/regions.py``), ``semijoin_probe_rows`` and
# ``semijoin_build_rows`` (the key rows each semi-join program is handed),
# ``range_index_selects`` (masked σ evaluations over the rows a range index
# selected, :func:`masked`) and ``range_full_selects`` (masked σ
# evaluations over full columns).
# ``ServingRuntime.metrics_snapshot()`` surfaces them as ``server_*``.
SERVER = MetricsRegistry()

# --------------------------------------------------------------------------
# Scalar expressions
# --------------------------------------------------------------------------

_SCALAR_FUNCS: Dict[str, Callable] = {
    "abs": jnp.abs,
    "sqrt": jnp.sqrt,
    "exp": jnp.exp,
    "log": jnp.log,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "neg": jnp.negative,
    "square": jnp.square,
    "mod100": lambda x: jnp.mod(x, 100),
}


def register_scalar_func(name: str, fn: Callable) -> None:
    _SCALAR_FUNCS[name] = fn


class Scalar:
    """Base class for scalar (per-row) expressions."""

    def eval(self, table: Table, params: Optional[Mapping[str, object]] = None):
        raise NotImplementedError

    def key(self) -> Tuple:
        raise NotImplementedError

    def columns(self) -> Tuple[str, ...]:
        return ()

    def __hash__(self):
        return hash(self.key())

    def __eq__(self, other):
        return isinstance(other, Scalar) and self.key() == other.key()

    # sugar
    def __add__(self, o):  return Arith("+", self, _wrap(o))
    def __radd__(self, o): return Arith("+", _wrap(o), self)
    def __sub__(self, o):  return Arith("-", self, _wrap(o))
    def __mul__(self, o):  return Arith("*", self, _wrap(o))
    def __truediv__(self, o): return Arith("/", self, _wrap(o))
    def eq(self, o):  return Cmp("==", self, _wrap(o))
    def ne(self, o):  return Cmp("!=", self, _wrap(o))
    def lt(self, o):  return Cmp("<", self, _wrap(o))
    def le(self, o):  return Cmp("<=", self, _wrap(o))
    def gt(self, o):  return Cmp(">", self, _wrap(o))
    def ge(self, o):  return Cmp(">=", self, _wrap(o))
    def and_(self, o): return BoolOp("and", self, _wrap(o))
    def or_(self, o):  return BoolOp("or", self, _wrap(o))


def _wrap(v) -> "Scalar":
    if isinstance(v, Scalar):
        return v
    return Lit(v)


@dataclasses.dataclass(frozen=True, eq=False)
class Col(Scalar):
    name: str

    def eval(self, table, params=None):
        return table.column(self.name)

    def key(self):
        return ("col", self.name)

    def columns(self):
        return (self.name,)

    def sql(self):
        return self.name


@dataclasses.dataclass(frozen=True, eq=False)
class Lit(Scalar):
    value: object

    def eval(self, table, params=None):
        return jnp.full((table.nrows,), self.value)

    def key(self):
        return ("lit", self.value)

    def sql(self):
        return repr(self.value)


@dataclasses.dataclass(frozen=True, eq=False)
class Param(Scalar):
    """A runtime parameter (e.g. the loop variable's field in a correlated query)."""

    name: str

    def eval(self, table, params=None):
        if params is None or self.name not in params:
            raise KeyError(f"unbound query parameter {self.name!r}")
        return jnp.full((table.nrows,), params[self.name])

    def key(self):
        return ("param", self.name)

    def sql(self):
        return f":{self.name}"


_ARITH = {
    "+": jnp.add, "-": jnp.subtract, "*": jnp.multiply, "/": jnp.divide,
    "min": jnp.minimum, "max": jnp.maximum,
}
_CMP = {
    "==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less, "<=": jnp.less_equal,
    ">": jnp.greater, ">=": jnp.greater_equal,
}


@dataclasses.dataclass(frozen=True, eq=False)
class Arith(Scalar):
    op: str
    left: Scalar
    right: Scalar

    def eval(self, table, params=None):
        return _ARITH[self.op](self.left.eval(table, params), self.right.eval(table, params))

    def key(self):
        return ("arith", self.op, self.left.key(), self.right.key())

    def columns(self):
        return self.left.columns() + self.right.columns()

    def sql(self):
        return f"({_sql(self.left)} {self.op} {_sql(self.right)})"


@dataclasses.dataclass(frozen=True, eq=False)
class Cmp(Scalar):
    op: str
    left: Scalar
    right: Scalar

    def eval(self, table, params=None):
        return _CMP[self.op](self.left.eval(table, params), self.right.eval(table, params))

    def key(self):
        return ("cmp", self.op, self.left.key(), self.right.key())

    def columns(self):
        return self.left.columns() + self.right.columns()

    def sql(self):
        op = {"==": "=", "!=": "<>"}.get(self.op, self.op)
        return f"{_sql(self.left)} {op} {_sql(self.right)}"


@dataclasses.dataclass(frozen=True, eq=False)
class BoolOp(Scalar):
    op: str  # "and" | "or"
    left: Scalar
    right: Scalar

    def eval(self, table, params=None):
        l = self.left.eval(table, params)
        r = self.right.eval(table, params)
        return jnp.logical_and(l, r) if self.op == "and" else jnp.logical_or(l, r)

    def key(self):
        return ("bool", self.op, self.left.key(), self.right.key())

    def columns(self):
        return self.left.columns() + self.right.columns()

    def sql(self):
        return f"({_sql(self.left)} {self.op.upper()} {_sql(self.right)})"


@dataclasses.dataclass(frozen=True, eq=False)
class Not(Scalar):
    child: Scalar

    def eval(self, table, params=None):
        return jnp.logical_not(self.child.eval(table, params))

    def key(self):
        return ("not", self.child.key())

    def columns(self):
        return self.child.columns()

    def sql(self):
        return f"NOT ({_sql(self.child)})"


@dataclasses.dataclass(frozen=True, eq=False)
class Func(Scalar):
    name: str
    args: Tuple[Scalar, ...]

    def eval(self, table, params=None):
        fn = _SCALAR_FUNCS[self.name]
        return fn(*[a.eval(table, params) for a in self.args])

    def key(self):
        return ("func", self.name, tuple(a.key() for a in self.args))

    def columns(self):
        out: Tuple[str, ...] = ()
        for a in self.args:
            out += a.columns()
        return out

    def sql(self):
        return f"{self.name}({', '.join(_sql(a) for a in self.args)})"


def _sql(e: Scalar) -> str:
    return e.sql() if hasattr(e, "sql") else repr(e)


# --------------------------------------------------------------------------
# Join index machinery (host-side; bulk gathers stay in jnp)
# --------------------------------------------------------------------------

def equi_join_indices(lk: np.ndarray, rk: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """All (li, ri) pairs with lk[li] == rk[ri], via sort+searchsorted."""
    lk = np.asarray(lk)
    rk = np.asarray(rk)
    order = np.argsort(rk, kind="stable")
    rk_sorted = rk[order]
    lo = np.searchsorted(rk_sorted, lk, side="left")
    hi = np.searchsorted(rk_sorted, lk, side="right")
    counts = hi - lo
    li = np.repeat(np.arange(len(lk)), counts)
    starts = np.repeat(lo, counts)
    base = np.repeat(np.cumsum(counts) - counts, counts)
    run_off = np.arange(len(li)) - base
    ri = order[starts + run_off]
    return li, ri


# --------------------------------------------------------------------------
# Query algebra
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AggSpec:
    func: str  # sum | count | min | max | avg
    col: Optional[str]  # None for count(*)
    out: str

    def key(self):
        return ("agg", self.func, self.col, self.out)

    def sql(self):
        arg = self.col if self.col is not None else "*"
        return f"{self.func}({arg}) AS {self.out}"


class Query:
    """Base class for relational algebra nodes."""

    def execute(self, db, params: Optional[Mapping[str, object]] = None) -> Table:
        raise NotImplementedError

    def key(self) -> Tuple:
        raise NotImplementedError

    def sql(self) -> str:
        raise NotImplementedError

    def children(self) -> Tuple["Query", ...]:
        return ()

    def output_schema(self, db) -> Schema:
        raise NotImplementedError

    def __hash__(self):
        return hash(self.key())

    def __eq__(self, other):
        return isinstance(other, Query) and self.key() == other.key()

    def __repr__(self):
        return f"{type(self).__name__}[{self.sql()}]"


@dataclasses.dataclass(frozen=True, eq=False)
class Scan(Query):
    table: str

    def execute(self, db, params=None):
        return db.table(self.table)

    def key(self):
        return ("scan", self.table)

    def sql(self):
        return f"SELECT * FROM {self.table}"

    def output_schema(self, db):
        return db.table(self.table).schema


@dataclasses.dataclass(frozen=True, eq=False)
class Select(Query):
    pred: Scalar
    child: Query

    def execute(self, db, params=None):
        t = self.child.execute(db, params)
        if t.nrows == 0:
            return record_rows(db, self, t)
        mask = self.pred.eval(t, params)
        return record_rows(
            db, self, t.filter_mask(to_host(mask, "algebra.select")))

    def key(self):
        return ("select", self.pred.key(), self.child.key())

    def children(self):
        return (self.child,)

    def sql(self):
        return f"SELECT * FROM ({self.child.sql()}) WHERE {_sql(self.pred)}"

    def output_schema(self, db):
        return self.child.output_schema(db)


@dataclasses.dataclass(frozen=True, eq=False)
class Project(Query):
    """π — keeps `cols` and adds computed columns {name: scalar expr}."""

    cols: Tuple[str, ...]
    child: Query
    computed: Tuple[Tuple[str, Scalar], ...] = ()

    def execute(self, db, params=None):
        t = self.child.execute(db, params)
        out = t.select_columns([c for c in self.cols]) if self.cols else t.select_columns([])
        for name, expr in self.computed:
            vals = expr.eval(t, params)
            dt = str(to_host(vals, "algebra.project").dtype)
            out = out.with_column(Field(name, dt), vals)
        return out

    def key(self):
        return ("project", self.cols, tuple((n, e.key()) for n, e in self.computed), self.child.key())

    def children(self):
        return (self.child,)

    def sql(self):
        items = list(self.cols) + [f"{_sql(e)} AS {n}" for n, e in self.computed]
        return f"SELECT {', '.join(items) or '*'} FROM ({self.child.sql()})"

    def output_schema(self, db):
        base = self.child.output_schema(db).subset(self.cols)
        for name, _ in self.computed:
            base = base.concat(Schema.of(Field(name, "float64")))
        return base


@dataclasses.dataclass(frozen=True, eq=False)
class Join(Query):
    """Inner equi-join on left.left_key == right.right_key."""

    left: Query
    right: Query
    left_key: str
    right_key: str

    def execute(self, db, params=None):
        lt = self.left.execute(db, params)
        rt = self.right.execute(db, params)
        li, ri = equi_join_indices(
            to_host(lt.column(self.left_key), "algebra.join"),
            to_host(rt.column(self.right_key), "algebra.join"))
        lsel = lt.take(li)
        rsel = rt.take(ri)
        # disambiguate duplicate names by prefixing right side
        lnames = set(lsel.schema.names)
        ren = {n: f"{rt.name}_{n}" for n in rsel.schema.names if n in lnames}
        rsel = rsel.rename(ren)
        cols = dict(lsel.columns)
        cols.update(rsel.columns)
        return record_rows(db, self, Table(f"{lt.name}_join_{rt.name}",
                                           lsel.schema.concat(rsel.schema),
                                           cols))

    def key(self):
        return ("join", self.left_key, self.right_key, self.left.key(), self.right.key())

    def children(self):
        return (self.left, self.right)

    def sql(self):
        return (f"SELECT * FROM ({self.left.sql()}) l JOIN ({self.right.sql()}) r "
                f"ON l.{self.left_key} = r.{self.right_key}")

    def output_schema(self, db):
        ls = self.left.output_schema(db)
        rs = self.right.output_schema(db)
        lnames = set(ls.names)
        rf = []
        rprefix = self.right.table if isinstance(self.right, Scan) else "r"
        for f in rs.fields:
            rf.append(dataclasses.replace(f, name=f"{rprefix}_{f.name}") if f.name in lnames else f)
        return ls.concat(Schema(tuple(rf)))


@dataclasses.dataclass(frozen=True, eq=False)
class SemiJoin(Query):
    """Left semi-join: the rows of ``left`` whose ``left_key`` equals the
    ``right_key`` of some row of ``right`` (SQL ``EXISTS``/``IN``), each
    once, in ``left``'s order, with ``left``'s columns.

    Runs on the device over the key columns of each side's selection and
    row mask (:func:`masked`: the base's rows, or those a range index
    picked); neither key column reaches the host. Integer keys with a
    small enough range probe a direct-address table of the right side,
    one jitted program a call (the table itself is built once per data
    epoch where the right side has no parameters); other keys take one
    jitted sort and search."""

    left: Query
    right: Query
    left_key: str
    right_key: str

    def execute(self, db, params=None):
        base, sel, mask = self.execute_masked(db, params)
        return record_rows(db, self,
                           base.take(selected_rows(base, sel, mask)))

    def execute_masked(self, db, params=None) -> Tuple[
            Table, Optional["RangeSelection"], jnp.ndarray]:
        """The left base table, the selection of its rows the probe is
        handed (None: every row) and the mask of those in the semi-join
        (the span ``server.semijoin``)."""
        lt, sel, lmask = masked(self.left, db, params)
        lkeys = lt.column(self.left_key)
        n_probe = lt.nrows if sel is None else sel.width
        if lmask is None:
            lmask = jnp.ones((n_probe,), jnp.bool_)
        SERVER.inc("semijoin_probe_rows", n_probe)
        SERVER.inc("exists_set", n_probe)
        with db.tracer.span("server.semijoin", probe_rows=n_probe):
            present = self._built(db, params) \
                if jnp.issubdtype(lkeys.dtype, jnp.integer) else None
            if present is None:
                rt, rsel, rmask = self._right(db, params)
                out, n = _semijoin_sorted(
                    _selected(lkeys, sel), lmask,
                    _selected(rt.column(self.right_key), rsel), rmask)
            elif sel is None:
                out, n = _semijoin_probe(lkeys, None, lmask, present,
                                         ordered=_KEY_FACTS(lkeys)[1])
            else:
                out, n = _semijoin_probe(sel.index.clustered(lkeys),
                                         sel.start, lmask, present,
                                         ordered=False)
            out.block_until_ready()
        record_rows(db, self, n)
        return lt, sel, out

    def _right(self, db, params) -> Tuple[
            Table, Optional["RangeSelection"], jnp.ndarray]:
        rt, sel, rmask = masked(self.right, db, params)
        n_build = rt.nrows if sel is None else sel.width
        if rmask is None:
            rmask = jnp.ones((n_build,), jnp.bool_)
        SERVER.inc("semijoin_build_rows", n_build)
        return rt, sel, rmask

    def _built(self, db, params) -> Optional[jnp.ndarray]:
        """The right side's direct-address table, or None where its keys
        do not fit one. A right side without parameters is built once per
        data epoch of its tables and kept."""
        key = None
        if not query_has_params(self.right):
            key = (db.instance_token, self.right.key(), self.right_key,
                   db.site_epoch(scan_tables(self.right)))
            hit = _BUILDS.get(key)
            if hit is not None:
                _BUILDS.move_to_end(key)
                present, rows = hit
                # the right side's rows as its build found them, for the
                # server's time model
                for node, n in zip(_preorder(self.right), rows):
                    if n is not None:
                        record_rows(db, node, n)
                return present
        rt, sel, rmask = self._right(db, params)
        rkeys = rt.column(self.right_key)
        space, ordered = _KEY_FACTS(rkeys) if rt.nrows else (1, False)
        if space is None or space > _MAX_SEMIJOIN_SPACE:
            return None
        if sel is not None:
            rkeys, ordered = sel.take(rkeys), False
        present = _semijoin_build(rkeys, rmask, space=space, ordered=ordered)
        rec = getattr(db, "_run_rows", None)
        if key is not None and rec is not None:
            _BUILDS[key] = (present, tuple(resolve_rows(
                [rec.get(id(node)) for node in _preorder(self.right)])))
            while len(_BUILDS) > _BUILDS_CAP:
                _BUILDS.popitem(last=False)
        return present

    def key(self):
        return ("semijoin", self.left_key, self.right_key, self.left.key(),
                self.right.key())

    def children(self):
        return (self.left, self.right)

    def sql(self):
        return (f"SELECT * FROM ({self.left.sql()}) l WHERE EXISTS "
                f"(SELECT 1 FROM ({self.right.sql()}) r "
                f"WHERE r.{self.right_key} = l.{self.left_key})")

    def output_schema(self, db):
        return self.left.output_schema(db)


# --------------------------------------------------------------------------
# Masked evaluation: a σ chain or a semi-join as a row mask over its base
# --------------------------------------------------------------------------

def record_rows(db, node: Query, rows):
    """Note the rows ``node`` produced in this query's run (a count, a row
    mask, or the result table itself, which is returned), for the server's
    time model (``DatabaseServer._true_times``), which then does not run
    the node again. A server without that record ignores it."""
    rec = getattr(db, "_run_rows", None)
    if rec is not None:
        rec[id(node)] = rows.nrows if isinstance(rows, Table) else rows
    return rows


def resolve_rows(values) -> list:
    """Row counts of noted values (see :func:`record_rows`; None stays
    None): counts as they are, masks and device counts read in one pull."""
    out = list(values)
    pending = [i for i, v in enumerate(out)
               if v is not None and not isinstance(v, int)]
    if pending:
        counts = to_host(jnp.stack([jnp.sum(out[i], dtype=jnp.int32)
                                    for i in pending]), "algebra.rows")
        for i, c in zip(pending, counts.tolist()):
            out[i] = c
    return out


def _preorder(q: Query):
    yield q
    for c in q.children():
        yield from _preorder(c)


def masked(q: Query, db, params=None) -> Tuple[
        Table, Optional["RangeSelection"], Optional[jnp.ndarray]]:
    """``q``'s rows as a base table, a selection of its rows (a
    :class:`RangeSelection`; None: every row) and a mask over that
    selection (None: all of it). A chain of σ over a scan, and a
    semi-join over such chains, are evaluated on the device; nothing
    compacts. A σ whose conjunction bounds one integer column of the base
    from both sides, by literals or parameters, and keeps at most an
    eighth of its rows, selects them through the column's sorted index
    (:func:`_range_selection`): the selection is padded to a power of
    two, so its shapes come in a few buckets a base, and the whole
    predicate is then evaluated on the selected rows. Any other σ runs
    over the base's full columns, one shape a base. Any other node is
    executed and its result taken whole."""
    if isinstance(q, Scan):
        return db.table(q.table), None, None
    if isinstance(q, Select):
        base, sel, mask = masked(q.child, db, params)
        if base.nrows == 0:
            return base, sel, mask
        if sel is None:
            with db.tracer.span("server.range_select"):
                sel = _range_selection(q.pred, base, params)
            if sel is not None and mask is not None:
                mask = mask.at[sel.rows()].get(mode="promise_in_bounds")
        mask = _pred_mask(q.pred, base, params, sel, mask)
        SERVER.inc("range_full_selects" if sel is None
                   else "range_index_selects")
        record_rows(db, q, mask)
        return base, sel, mask
    if isinstance(q, SemiJoin):
        return q.execute_masked(db, params)
    return q.execute(db, params), None, None


def selected_rows(base: Table, sel, mask) -> np.ndarray:
    """The host row ids of ``base`` that a selection and its mask keep
    (see :func:`masked`), in the base's order: one pull."""
    if sel is None:
        return np.flatnonzero(to_host(mask, "algebra.semijoin"))
    ids = to_host(jnp.where(mask, sel.rows(), -1), "algebra.semijoin")
    return np.sort(ids[ids >= 0])


def _window(col, start, width: int):
    """``col[start:start + width]`` (``start`` a traced value inside a
    jitted program), or ``col`` where ``start`` is None."""
    return col if start is None else \
        jax.lax.dynamic_slice_in_dim(col, start, width)


def _selected(col, sel):
    """``col`` at the rows of a selection (None: ``col``)."""
    return col if sel is None else sel.take(col)


class _Columns:
    """The columns a predicate reads, as it reads a table's, inside a
    jitted program."""

    def __init__(self, columns: Dict[str, jnp.ndarray], nrows: int):
        self.columns = columns
        self.nrows = nrows

    def column(self, name: str):
        return self.columns[name]


def _param_names(s: Scalar):
    if isinstance(s, Param):
        yield s.name
    for k in _embedded_scalars(s):
        yield from _param_names(k)


# one jitted program per predicate (by its structure), or None where the
# predicate does not trace (a registered function of host Python)
_PRED_PROGRAMS: "OrderedDict[tuple, Optional[Callable]]" = OrderedDict()
_PRED_PROGRAMS_CAP = 256


def _pred_run(pred: Scalar, columns, values, mask, window, nrows):
    """``pred`` over ``nrows`` rows of ``columns``, and-ed into ``mask``:
    every row (``window`` None), or the window ``(start, lo, hi)`` of
    columns in a range index's order, its rows ``[lo, hi)`` in the
    range."""
    if window is not None:
        start, lo, hi = window
        columns = {c: _window(v, start, nrows) for c, v in columns.items()}
        i = jnp.arange(nrows, dtype=jnp.int32)
        valid = (i >= lo) & (i < hi)
        mask = valid if mask is None else jnp.logical_and(mask, valid)
    m = pred.eval(_Columns(columns, nrows), values)
    return m if mask is None else jnp.logical_and(mask, m)


def _pred_mask(pred: Scalar, base: Table, params, sel, mask):
    """``pred`` over the rows of ``base`` in the selection ``sel`` (None:
    every row), and-ed into ``mask``: one jitted program a shape, its
    parameters and the selection's offsets passed as values (a new
    binding compiles nothing)."""
    columns = {c: base.column(c) for c in set(pred.columns())}
    window, nrows = None, base.nrows
    if sel is not None:
        columns = {c: sel.index.clustered(v) for c, v in columns.items()}
        window, nrows = (sel.start, sel.lo, sel.hi), sel.width
    values = {}
    for n in set(_param_names(pred)):
        if params is None or n not in params:
            raise KeyError(f"unbound query parameter {n!r}")
        values[n] = params[n]
    key = pred.key()
    fn = _PRED_PROGRAMS.get(key, False)
    if fn is False:
        def run(columns, values, mask, window, nrows):
            return _pred_run(pred, columns, values, mask, window, nrows)
        fn = _PRED_PROGRAMS[key] = jax.jit(run, static_argnums=(4,))
        while len(_PRED_PROGRAMS) > _PRED_PROGRAMS_CAP:
            _PRED_PROGRAMS.popitem(last=False)
    if fn is not None:
        try:
            return fn(columns, values, mask, window, nrows)
        except Exception:   # not traceable: evaluate op by op from now on
            _PRED_PROGRAMS[key] = None
    return _pred_run(pred, columns, values, mask, window, nrows)


# --------------------------------------------------------------------------
# Range selection through a sorted index of one column
# --------------------------------------------------------------------------

_FLIPPED = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}


def _conjuncts(s: Scalar):
    if isinstance(s, BoolOp) and s.op == "and":
        yield from _conjuncts(s.left)
        yield from _conjuncts(s.right)
    else:
        yield s


def _integer_bound(s: Scalar, params) -> Optional[int]:
    """The value of a literal or bound parameter that is an integer."""
    if isinstance(s, Lit):
        v = s.value
    elif isinstance(s, Param) and params is not None and s.name in params:
        v = params[s.name]
    else:
        return None
    if isinstance(v, (bool, np.bool_)) or not isinstance(v, (int, np.integer)):
        return None
    return int(v)


def _range_bounds(pred: Scalar, params) -> Dict[str, Tuple[int, int]]:
    """The inclusive bounds ``[low, high]`` a conjunction sets on each
    column it compares with integer literals or parameters from both
    sides (``c >= a``, ``c > a``, ``c < b``, ``c <= b``, either side of
    the comparison); the tightest where it sets several."""
    low: Dict[str, int] = {}
    high: Dict[str, int] = {}
    for c in _conjuncts(pred):
        if not isinstance(c, Cmp) or c.op not in _FLIPPED:
            continue
        column, bound, op = c.left, c.right, c.op
        if not isinstance(column, Col):
            column, bound, op = c.right, c.left, _FLIPPED[op]
        v = _integer_bound(bound, params) if isinstance(column, Col) \
            else None
        if v is None:
            continue
        name = column.name
        if op in (">", ">="):
            v += op == ">"
            low[name] = max(low.get(name, v), v)
        else:
            v -= op == "<"
            high[name] = min(high.get(name, v), v)
    return {n: (low[n], high[n]) for n in low if n in high}


def search_key(keys: np.ndarray, key_val):
    """``key_val`` in the sorted ``keys``' own dtype, or None where that
    dtype cannot hold it exactly (such a key matches nothing). Searching
    in the keys' dtype keeps numpy from promoting and copying them all."""
    try:
        k = keys.dtype.type(key_val)
    except (TypeError, ValueError, OverflowError):
        return None
    return k if k.item() == key_val else None


def _bound_position(values: np.ndarray, bound: int, side: str) -> int:
    """``np.searchsorted(values, bound, side)`` over a sorted integer
    column, searched in its own dtype (see :func:`search_key`); a bound
    that dtype cannot hold lies past the edge it is beyond."""
    k = search_key(values, bound)
    if k is None:
        return 0 if bound < np.iinfo(values.dtype).min else values.size
    return int(np.searchsorted(values, k, side))


class _RangeIndex:
    """A column's row ids in the order of their values (a stable argsort,
    on the device) and the values in that order (host); and, made at a
    column's first use, copies of its table's columns in that order
    (device), so that a window of the index reads each column as one
    contiguous slice and not as a gather, which costs a TPU v5e ~8 ns an
    index whatever their number. Sorted and copied on the host (~0.2 s
    for 1.5M keys), where nothing compiles: the TPU compiler takes ~30 s
    over a device sort of as many."""

    def __init__(self, col):
        values = to_host(col, "algebra.range_index")
        perm = np.argsort(values, kind="stable").astype(np.int32)
        self.values = values[perm]
        self.perm = to_device(perm, None, "algebra.range_index")
        self.clustered = TableMemo(lambda c: to_device(
            to_host(c, "algebra.range_index")[perm], None,
            "algebra.range_index"), 16)


# the index of a column, once per column array: a new data epoch is a new
# array, and builds a new index
_RANGE_INDEX = TableMemo(_RangeIndex, 16)


@dataclasses.dataclass(frozen=True, eq=False)
class RangeSelection:
    """The rows of a base table that a range over one of its columns
    keeps, as a window of that column's :class:`_RangeIndex`: index
    positions ``start`` to ``start + width``, of which ``[lo, hi)`` are in
    the range. ``width`` is the least power of two that holds them."""

    index: _RangeIndex
    start: int
    lo: int
    hi: int
    width: int

    def take(self, col):
        """``col`` (a column of the base) at the selected rows."""
        return _window(self.index.clustered(col), self.start, self.width)

    def rows(self):
        """The selected rows' ids in the base (device)."""
        return _window(self.index.perm, self.start, self.width)


def _range_selection(pred: Scalar, base: Table, params):
    """The :class:`RangeSelection` of the rows of ``base`` in a range
    ``pred`` sets on an integer column from both sides, where they are at
    most an eighth of the base's (the bounds are searched in the column's
    sorted index on the host, so this is known before any device work);
    of several such columns the one with the fewest rows. None
    otherwise."""
    best = None
    for name, (low, high) in _range_bounds(pred, params).items():
        if name not in base.columns:
            continue
        col = base.column(name)
        if not jnp.issubdtype(col.dtype, jnp.integer):
            continue
        info = jnp.iinfo(col.dtype)
        if low < info.min or high > info.max:
            continue
        index = _RANGE_INDEX(col)
        lo = _bound_position(index.values, low, "left")
        n = max(_bound_position(index.values, high, "right") - lo, 0)
        if n <= base.nrows / 8 and (best is None or n < best[0]):
            best = (n, index, lo)
    if best is None:
        return None
    n, index, lo = best
    width = 1 << max(n - 1, 0).bit_length()
    start = min(lo, base.nrows - width)
    return RangeSelection(index, start, lo - start, lo - start + n, width)


def _key_facts(col) -> Tuple[Optional[int], bool]:
    """Of an integer key column: the slots of a direct-address table over
    it (its largest key + 1; None where a key is negative), and whether
    its keys are in ascending order. One pull of three scalars a column,
    memoized by its array; (None, False) for other keys."""
    if not jnp.issubdtype(col.dtype, jnp.integer) or col.shape[0] == 0:
        return None, False
    lo, hi, ordered = to_host(jnp.stack([
        jnp.min(col), jnp.max(col),
        jnp.all(col[1:] >= col[:-1]).astype(col.dtype)]),
        "algebra.semijoin").tolist()
    return (None if lo < 0 else int(hi) + 1), bool(ordered)


_KEY_FACTS = TableMemo(_key_facts, 64)

# largest direct-address table the semi-join builds: 2**28 one-byte slots
_MAX_SEMIJOIN_SPACE = 1 << 28
# the build sides kept: a build side with no parameters is the same for
# every execution over the same data, so its table is built once per
# data epoch
_BUILDS_CAP = 16
_BUILDS: "OrderedDict[tuple, jnp.ndarray]" = OrderedDict()


@functools.partial(jax.jit, static_argnames=("space", "ordered"))
def _semijoin_build(rkeys, rmask, space, ordered):
    """The build side as a direct-address table of ``space`` slots: slot
    ``k`` is set where some row under ``rmask`` has key ``k``. Keys in
    ascending order take the TPU's sorted scatter, which compiles in
    seconds where the general one takes ~25 s for 6M keys."""
    if ordered:
        return jnp.zeros(space, jnp.bool_).at[rkeys].max(
            rmask, mode="drop", indices_are_sorted=True)
    slot = jnp.where(rmask, rkeys, space)
    return jnp.zeros(space, jnp.bool_).at[slot].set(True, mode="drop")


@functools.partial(jax.jit, static_argnames=("ordered",))
def _semijoin_probe(lkeys, start, lmask, present, ordered):
    """Each probe row under ``lmask`` whose key has its slot set: the
    window of ``lkeys`` from ``start`` (None: every row)."""
    lkeys = _window(lkeys, start, lmask.shape[0])
    space = present.shape[0]
    hit = jnp.take(present, jnp.clip(lkeys, 0, space - 1),
                   indices_are_sorted=ordered) & (lkeys >= 0) \
        & (lkeys < space)
    out = lmask & hit
    return out, jnp.sum(out, dtype=jnp.int32)


@jax.jit
def _semijoin_sorted(lkeys, lmask, rkeys, rmask):
    """Semi-join as one program for any key type: the build keys sorted,
    each key's rows in the build mask first, then each probe key searched
    for its first row."""
    keys, dropped = jax.lax.sort(
        (rkeys, jnp.logical_not(rmask).astype(jnp.int8)), num_keys=2)
    pos = jnp.clip(jnp.searchsorted(keys, lkeys), 0, keys.shape[0] - 1)
    out = lmask & (keys[pos] == lkeys) & (dropped[pos] == 0)
    return out, jnp.sum(out, dtype=jnp.int32)


def _embedded_scalars(node):
    """Every Scalar hanging off one dataclass node — covers predicates,
    computed-projection pairs, and whatever scalar slots future operators
    add, without naming fields."""
    for f in dataclasses.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, Scalar):
            yield v
        elif isinstance(v, tuple):
            for item in v:
                if isinstance(item, Scalar):
                    yield item
                elif isinstance(item, tuple):
                    yield from (x for x in item if isinstance(x, Scalar))


def query_has_params(q: "Query") -> bool:
    """True iff a relational tree contains a ``Param`` anywhere (predicates
    and computed projections included) — the sites whose bindings may differ
    between batched invocations, so they never amortize."""
    def scalar_has(s: Scalar) -> bool:
        if isinstance(s, Param):
            return True
        return any(scalar_has(k) for k in _embedded_scalars(s))

    if any(scalar_has(s) for s in _embedded_scalars(q)):
        return True
    return any(query_has_params(c) for c in q.children())


_AGG_FUNCS = {
    "sum": lambda x: jnp.sum(x),
    "min": lambda x: jnp.min(x),
    "max": lambda x: jnp.max(x),
    "avg": lambda x: jnp.mean(x),
}


def _group_codes(col) -> Tuple[np.ndarray, jnp.ndarray]:
    """A group-by column's distinct values (host) and each row's index
    among them (device), once per column array."""
    uniq, inv = np.unique(to_host(col, "algebra.aggregate"),
                          return_inverse=True)
    return uniq, to_device(inv.reshape(-1).astype(np.int32), None,
                           "algebra.aggregate")


_GROUP_CODES = TableMemo(_group_codes, 64)


# up to this many groups, grouped sums compare each row with each group
# instead of scattering (a scatter-add into few slots is slow on the TPU)
_COMPARE_GROUPS = 64


@functools.partial(jax.jit, static_argnames=("n_groups",))
def _masked_group_aggs(mask, codes, values, start, n_groups):
    """Count and sums by group of the rows under ``mask``: the window of
    the columns from ``start`` (None: every row)."""
    codes = _window(codes, start, mask.shape[0])
    values = tuple(_window(v, start, mask.shape[0]) for v in values)
    if n_groups <= _COMPARE_GROUPS:
        member = (codes[None, :] == jnp.arange(n_groups, dtype=codes.dtype)
                  [:, None]) & mask[None, :]
        counts = jnp.sum(member, axis=1, dtype=jnp.int32)
        sums = tuple(jnp.sum(jnp.where(member, v[None, :], 0), axis=1,
                             dtype=v.dtype) for v in values)
        return counts, sums
    counts = jax.ops.segment_sum(mask.astype(jnp.int32), codes, n_groups)
    sums = tuple(jax.ops.segment_sum(jnp.where(mask, v, jnp.zeros_like(v)),
                                     codes, n_groups) for v in values)
    return counts, sums


@dataclasses.dataclass(frozen=True, eq=False)
class Aggregate(Query):
    """γ — group-by aggregation. Empty group_by = single global group."""

    group_by: Tuple[str, ...]
    aggs: Tuple[AggSpec, ...]
    child: Query

    def execute(self, db, params=None):
        if isinstance(self.child, (Select, SemiJoin)) \
                and len(self.group_by) == 1 \
                and all(a.func in ("count", "sum") for a in self.aggs):
            return record_rows(db, self, self._grouped_masked(
                *masked(self.child, db, params)))
        t = self.child.execute(db, params)
        if not self.group_by:
            return record_rows(db, self, self._global(t))
        return record_rows(db, self, self._grouped(t))

    def _grouped_masked(self, base: Table, sel, mask) -> Table:
        """Grouped count/sum over the rows of ``base`` in the selection
        ``sel`` (None: every row) under ``mask`` (see :func:`masked`), on
        the device: one program of the selection's shape, whatever the
        rows that pass. The groups are the base column's distinct values
        that have rows, in order, as :meth:`_grouped` gives them."""
        (g,) = self.group_by
        uniq, codes = _GROUP_CODES(base.column(g))
        values = tuple(base.column(a.col) for a in self.aggs
                       if a.func == "sum")
        start = None
        if sel is not None:
            clustered = sel.index.clustered
            codes, start = clustered(codes), sel.start
            values = tuple(clustered(v) for v in values)
        if mask is None:
            mask = jnp.ones(codes.shape, jnp.bool_)
        counts, sums = _masked_group_aggs(mask, codes, values, start,
                                          n_groups=len(uniq))
        counts = to_host(counts, "algebra.aggregate")
        present = counts > 0
        fields, cols = [base.schema.field(g)], {g: uniq[present]}
        sums = iter(sums)
        for a in self.aggs:
            vals = counts if a.func == "count" else \
                to_host(next(sums), "algebra.aggregate")
            fields.append(Field(a.out, str(vals.dtype)))
            cols[a.out] = vals[present]
        return Table("agg", Schema(tuple(fields)), cols)

    def _global(self, t: Table) -> Table:
        fields, cols = [], {}
        for a in self.aggs:
            if a.func == "count":
                val, dt = t.nrows, "int32"
            else:
                arr = t.column(a.col)
                if t.nrows == 0:
                    val, dt = 0, "float32"
                else:
                    val = _AGG_FUNCS[a.func](arr)
                    dt = "float32" if a.func == "avg" else str(
                        to_host(val, "algebra.aggregate").dtype)
            fields.append(Field(a.out, dt))
            cols[a.out] = np.asarray([val], dtype=np.dtype(dt) if np.dtype(dt).itemsize<8 else np.dtype(dt.replace("64","32")))
        return Table("agg", Schema(tuple(fields)), cols)

    def _grouped(self, t: Table) -> Table:
        keys = [to_host(t.column(g), "algebra.aggregate")
                for g in self.group_by]
        if t.nrows == 0:
            uniq_idx = np.asarray([], dtype=np.int64)
            inv = np.asarray([], dtype=np.int64)
            ngroups = 0
        else:
            stacked = np.stack(keys, axis=1)
            _, uniq_idx, inv = np.unique(stacked, axis=0, return_index=True, return_inverse=True)
            inv = inv.reshape(-1)
            ngroups = int(inv.max()) + 1 if len(inv) else 0
        fields, cols = [], {}
        for g in self.group_by:
            f = None
            for tf in t.schema.fields:
                if tf.name == g:
                    f = tf
            fields.append(f)
            cols[g] = to_host(t.column(g), "algebra.aggregate")[uniq_idx]
        seg = to_device(inv, None, "algebra.aggregate")
        for a in self.aggs:
            if a.func == "count":
                vals = jax.ops.segment_sum(jnp.ones((t.nrows,), jnp.int32), seg, ngroups)
                dt = "int32"
            else:
                arr = t.column(a.col)
                if a.func == "sum":
                    vals = jax.ops.segment_sum(arr, seg, ngroups)
                elif a.func == "min":
                    vals = jax.ops.segment_min(arr, seg, ngroups)
                elif a.func == "max":
                    vals = jax.ops.segment_max(arr, seg, ngroups)
                elif a.func == "avg":
                    s = jax.ops.segment_sum(arr.astype(jnp.float32), seg, ngroups)
                    c = jax.ops.segment_sum(jnp.ones((t.nrows,), jnp.float32), seg, ngroups)
                    vals = s / jnp.maximum(c, 1.0)
                else:
                    raise ValueError(a.func)
                dt = "float32" if a.func == "avg" else str(
                    to_host(vals, "algebra.aggregate").dtype)
            fields.append(Field(a.out, dt))
            cols[a.out] = vals
        return Table("agg", Schema(tuple(fields)), cols)

    def key(self):
        return ("aggregate", self.group_by, tuple(a.key() for a in self.aggs), self.child.key())

    def children(self):
        return (self.child,)

    def sql(self):
        items = list(self.group_by) + [a.sql() for a in self.aggs]
        gb = f" GROUP BY {', '.join(self.group_by)}" if self.group_by else ""
        return f"SELECT {', '.join(items)} FROM ({self.child.sql()}){gb}"

    def output_schema(self, db):
        base = self.child.output_schema(db).subset(self.group_by) if self.group_by else Schema(())
        for a in self.aggs:
            base = base.concat(Schema.of(Field(a.out, "float64")))
        return base


@dataclasses.dataclass(frozen=True, eq=False)
class OrderBy(Query):
    keys: Tuple[str, ...]
    child: Query
    descending: bool = False

    def execute(self, db, params=None):
        return self.child.execute(db, params).sort_by(self.keys, self.descending)

    def key(self):
        return ("orderby", self.keys, self.descending, self.child.key())

    def children(self):
        return (self.child,)

    def sql(self):
        d = " DESC" if self.descending else ""
        return f"{self.child.sql()} ORDER BY {', '.join(self.keys)}{d}"

    def output_schema(self, db):
        return self.child.output_schema(db)


@dataclasses.dataclass(frozen=True, eq=False)
class Limit(Query):
    k: int
    child: Query

    def execute(self, db, params=None):
        return self.child.execute(db, params).head(self.k)

    def key(self):
        return ("limit", self.k, self.child.key())

    def children(self):
        return (self.child,)

    def sql(self):
        return f"{self.child.sql()} LIMIT {self.k}"

    def output_schema(self, db):
        return self.child.output_schema(db)


def scan_tables(q: Query) -> Tuple[str, ...]:
    """All base tables a relational ``Query`` tree scans (sorted).

    The canonical table-extraction walk: plan-cache stats tokens
    (``repro.api.cache.query_tables``), the serving-level site cache's
    invalidation epochs, and the cost model's binding-diversity group keys
    all share this identity so a table name means the same thing in every
    layer."""
    out = set()

    def walk(node: Query):
        if isinstance(node, Scan):
            out.add(node.table)
        for c in node.children():
            walk(c)

    walk(q)
    return tuple(sorted(out))
