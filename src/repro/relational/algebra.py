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
# ``semijoin_build_rows`` (the key rows each semi-join reads).
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

    Runs on the device over the key columns and their row masks
    (:func:`masked`); neither key column reaches the host. Integer keys
    with a small enough range probe a direct-address table of the right
    side, one jitted program a call (the table itself is built once per
    data epoch where the right side has no parameters); other keys take
    one jitted sort and search."""

    left: Query
    right: Query
    left_key: str
    right_key: str

    def execute(self, db, params=None):
        base, mask = self.execute_masked(db, params)
        return record_rows(db, self,
                           base.filter_mask(to_host(mask, "algebra.semijoin")))

    def execute_masked(self, db, params=None) -> Tuple[Table, jnp.ndarray]:
        """The left base table and the mask of its rows that are in the
        semi-join (the span ``server.semijoin``)."""
        lt, lmask = masked(self.left, db, params)
        lkeys = lt.column(self.left_key)
        if lmask is None:
            lmask = jnp.ones(lkeys.shape, jnp.bool_)
        SERVER.inc("semijoin_probe_rows", lt.nrows)
        SERVER.inc("exists_set", lt.nrows)
        with db.tracer.span("server.semijoin", probe_rows=lt.nrows):
            present = self._built(db, params) \
                if jnp.issubdtype(lkeys.dtype, jnp.integer) else None
            if present is not None:
                out, n = _semijoin_probe(lkeys, lmask, present,
                                         ordered=_KEY_FACTS(lkeys)[1])
            else:
                rt, rmask = self._right(db, params)
                out, n = _semijoin_sorted(lkeys, lmask,
                                          rt.column(self.right_key), rmask)
            out.block_until_ready()
        record_rows(db, self, n)
        return lt, out

    def _right(self, db, params) -> Tuple[Table, jnp.ndarray]:
        rt, rmask = masked(self.right, db, params)
        if rmask is None:
            rmask = jnp.ones((rt.nrows,), jnp.bool_)
        SERVER.inc("semijoin_build_rows", rt.nrows)
        return rt, rmask

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
        rt, rmask = self._right(db, params)
        rkeys = rt.column(self.right_key)
        space, ordered = _KEY_FACTS(rkeys) if rt.nrows else (1, False)
        if space is None or space > _MAX_SEMIJOIN_SPACE:
            return None
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


def masked(q: Query, db, params=None) -> Tuple[Table, Optional[jnp.ndarray]]:
    """``q``'s rows as a base table and a row mask over it (None: every
    row). A chain of σ over a scan, and a semi-join over such chains, are
    evaluated on the device over their base table's full columns, so no
    shape depends on the data: nothing compacts, and nothing compiles per
    result size. Any other node is executed and its result taken whole."""
    if isinstance(q, Scan):
        return db.table(q.table), None
    if isinstance(q, Select):
        base, mask = masked(q.child, db, params)
        if base.nrows == 0:
            return base, mask
        mask = _pred_mask(q.pred, base, params, mask)
        record_rows(db, q, mask)
        return base, mask
    if isinstance(q, SemiJoin):
        return q.execute_masked(db, params)
    return q.execute(db, params), None


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


def _pred_mask(pred: Scalar, base: Table, params, mask):
    """``pred`` over every row of ``base``, and-ed into ``mask``: one
    jitted program of the base's shape, its parameters passed as values
    (a new binding compiles nothing)."""
    key = pred.key()
    fn = _PRED_PROGRAMS.get(key, False)
    if fn is False:
        def run(columns, values, mask, nrows):
            m = pred.eval(_Columns(columns, nrows), values)
            return m if mask is None else jnp.logical_and(mask, m)
        fn = _PRED_PROGRAMS[key] = jax.jit(run, static_argnums=(3,))
        while len(_PRED_PROGRAMS) > _PRED_PROGRAMS_CAP:
            _PRED_PROGRAMS.popitem(last=False)
    if fn is not None:
        columns = {c: base.column(c) for c in set(pred.columns())}
        values = {}
        for n in set(_param_names(pred)):
            if params is None or n not in params:
                raise KeyError(f"unbound query parameter {n!r}")
            values[n] = params[n]
        try:
            return fn(columns, values, mask, base.nrows)
        except Exception:   # not traceable: evaluate op by op from now on
            _PRED_PROGRAMS[key] = None
    m = pred.eval(base, params)
    return m if mask is None else jnp.logical_and(mask, m)


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
def _semijoin_probe(lkeys, lmask, present, ordered):
    """Each probe row under ``lmask`` whose key has its slot set."""
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
def _masked_group_aggs(mask, codes, values, n_groups):
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

    def _grouped_masked(self, base: Table, mask) -> Table:
        """Grouped count/sum over the rows of ``base`` under ``mask``, on
        the device over the full columns: one program of the base's shape,
        whatever the rows that pass. The groups are the base column's
        distinct values that have rows, in order, as :meth:`_grouped`
        gives them."""
        (g,) = self.group_by
        uniq, codes = _GROUP_CODES(base.column(g))
        if mask is None:
            mask = jnp.ones(codes.shape, jnp.bool_)
        values = tuple(base.column(a.col) for a in self.aggs
                       if a.func == "sum")
        counts, sums = _masked_group_aggs(mask, codes, values,
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
