"""A σ whose conjunction bounds one integer column from both sides is
evaluated through a sorted index of that column when the range keeps at
most an eighth of the rows (``relational/algebra.py`` ``masked``,
``_range_selection``). On random columns and ranges, that path gives the
same kept rows, row counts, semi-join results, grouped counts and sums and
server times as the full-column path, which the tests reach by turning
the range selection off.
"""

import numpy as np
import pytest

from repro.relational import DatabaseServer, Field, Schema, Table
from repro.relational import algebra
from repro.relational.algebra import (SERVER, AggSpec, Aggregate, BoolOp, Cmp,
                                      Col, Lit, Param, Scan, Select, SemiJoin)

N = 2000
SEED = 2**31 + 1717


def _table(name, **cols):
    schema = Schema.of(*(Field(c, "int32", 4) for c in cols))
    return Table.from_columns(name, schema, **cols)


def _data(rng, n=N, n_values=400):
    """``t``: ascending keys with gaps, a date-like column with duplicates,
    five groups, values; ``r``: a build side over some of the keys."""
    i = np.arange(n)
    k = (i // 8) * 32 + i % 8 + 1
    rk = rng.choice(k, 3 * n // 2)
    return {"t": {"k": k, "d": rng.integers(0, n_values, n),
                  "g": rng.integers(0, 5, n), "v": rng.integers(-50, 50, n)},
            "r": {"rk": np.sort(rk), "late": rng.integers(0, 2, rk.size)}}


def _db(data):
    return DatabaseServer({name: _table(name, **cols)
                           for name, cols in data.items()})


def _range(lo_op="ge", hi_op="lt", flipped=False):
    """``d`` between the parameters ``lo`` and ``hi``, in the given form."""
    ops = {"ge": ">=", "gt": ">", "lt": "<", "le": "<="}
    lo = Cmp(ops[lo_op], Col("d"), Param("lo"))
    hi = Cmp(ops[hi_op], Col("d"), Param("hi"))
    if flipped:     # a <= d, b > d: the bound on the left
        lo = Cmp(algebra._FLIPPED[ops[lo_op]], Param("lo"), Col("d"))
        hi = Cmp(algebra._FLIPPED[ops[hi_op]], Param("hi"), Col("d"))
    return BoolOp("and", hi, lo) if flipped else BoolOp("and", lo, hi)


def _numpy_range(d, lo, hi, lo_op="ge", hi_op="lt"):
    keep_lo = d >= lo if lo_op == "ge" else d > lo
    keep_hi = d < hi if hi_op == "lt" else d <= hi
    return keep_lo & keep_hi


def _kept(db, query, params):
    """The base rows ``masked`` keeps (host ids in base order), and the
    selection's width (None: every row)."""
    base, sel, mask = algebra.masked(query, db, params)
    if mask is None:
        return np.arange(base.nrows), None
    return algebra.selected_rows(base, sel, mask), \
        None if sel is None else sel.width


def _both(monkeypatch, fn):
    """``fn()`` through the range index where it applies, then with every
    σ over full columns."""
    indexed = fn()
    with monkeypatch.context() as m:
        m.setattr(algebra, "_range_selection", lambda *a: None)
        full = fn()
    return indexed, full


def _counts():
    return (SERVER.value("range_index_selects"),
            SERVER.value("range_full_selects"))


def _semi(pred):
    return SemiJoin(Select(pred, Scan("t")),
                    Select(Cmp("==", Col("late"), Lit(1)), Scan("r")),
                    "k", "rk")


def _grouped(pred):
    return Aggregate(("g",), (AggSpec("count", None, "n"),
                              AggSpec("sum", "v", "s")), _semi(pred))


def _table_values(t: Table) -> dict:
    return {n: np.asarray(c).tolist() for n, c in t.columns.items()}


def _check_all_paths(monkeypatch, db, data, pred, params, want):
    """Kept rows, semi-join, grouped aggregate and the server's times
    agree between the two paths and with numpy."""
    (ids, width), (full_ids, full_width) = _both(
        monkeypatch, lambda: _kept(db, Select(pred, Scan("t")), params))
    assert full_width is None
    assert ids.tolist() == full_ids.tolist() == np.flatnonzero(want).tolist()

    t, r = data["t"], data["r"]
    in_semi = want & np.isin(t["k"], r["rk"][r["late"] == 1])
    semi, full_semi = _both(monkeypatch,
                            lambda: _semi(pred).execute(db, params))
    assert _table_values(semi) == _table_values(full_semi)
    assert _table_values(semi)["k"] == t["k"][in_semi].tolist()

    (agg, first, last), (full_agg, full_first, full_last) = _both(
        monkeypatch, lambda: db.run(_grouped(pred), params))
    assert _table_values(agg) == _table_values(full_agg)
    assert (first, last) == (full_first, full_last)
    groups = np.unique(t["g"][in_semi])
    assert _table_values(agg) == {
        "g": groups.tolist(),
        "n": [int(np.sum(in_semi & (t["g"] == g))) for g in groups],
        "s": [int(np.sum(t["v"][in_semi & (t["g"] == g)])) for g in groups]}
    return width


@pytest.mark.parametrize("case", range(8))
@pytest.mark.parametrize("form", [("ge", "lt", False), ("gt", "le", False),
                                  ("ge", "le", True), ("gt", "lt", True)])
def test_random_ranges_match_the_full_path(monkeypatch, form, case):
    rng = np.random.default_rng(SEED + case)
    data = _data(rng)
    db = _db(data)
    lo_op, hi_op, flipped = form
    lo = int(rng.integers(-5, 400))
    hi = lo + int(rng.integers(0, 45))
    want = _numpy_range(data["t"]["d"], lo, hi, lo_op, hi_op)
    before = _counts()
    width = _check_all_paths(monkeypatch, db, data,
                             _range(lo_op, hi_op, flipped),
                             {"lo": lo, "hi": hi}, want)
    assert width is not None and width >= max(int(want.sum()), 1)
    assert _counts()[0] > before[0]


@pytest.mark.parametrize("window", ["empty", "inverted", "below", "above",
                                    "first", "last", "one_value"])
def test_edge_windows(monkeypatch, window):
    """Empty and inverted windows, windows past either end of the values
    and at them, and a window of one value, whose duplicates all sit at
    the bounds."""
    rng = np.random.default_rng(SEED)
    data = _data(rng)
    db = _db(data)
    d = data["t"]["d"]
    lo, hi = {"empty": (100, 100), "inverted": (120, 90),
              "below": (-40, -2), "above": (400, 460),
              "first": (-10, 20), "last": (380, 1000),
              "one_value": (int(d[7]), int(d[7]) + 1)}[window]
    want = _numpy_range(d, lo, hi)
    width = _check_all_paths(monkeypatch, db, data, _range(),
                             {"lo": lo, "hi": hi}, want)
    assert width == max(1, 1 << max(int(want.sum()) - 1, 0).bit_length())


@pytest.mark.parametrize("literal", [False, True])
def test_a_range_and_another_conjunct(monkeypatch, literal):
    """The whole predicate is evaluated on the selected rows: the other
    conjunct still filters, and literal bounds select like parameters."""
    rng = np.random.default_rng(SEED + 1)
    data = _data(rng)
    db = _db(data)
    lo, hi = 150, 180
    bounds = (Lit(lo), Lit(hi)) if literal else (Param("lo"), Param("hi"))
    pred = BoolOp("and", Cmp(">", Col("v"), Lit(0)),
                  BoolOp("and", Cmp(">=", Col("d"), bounds[0]),
                         Cmp("<", Col("d"), bounds[1])))
    t = data["t"]
    want = (t["v"] > 0) & _numpy_range(t["d"], lo, hi)
    width = _check_all_paths(monkeypatch, db, data, pred,
                             {"lo": lo, "hi": hi}, want)
    assert width is not None


@pytest.mark.parametrize("pred", ["one_sided", "not_equal", "two_columns",
                                  "or", "float_bound", "out_of_int32"])
def test_other_predicates_take_the_full_path(monkeypatch, pred):
    rng = np.random.default_rng(SEED + 2)
    data = _data(rng)
    db = _db(data)
    t = data["t"]
    d, v = t["d"], t["v"]
    p, want = {
        "one_sided": (Cmp(">=", Col("d"), Lit(390)), d >= 390),
        "not_equal": (BoolOp("and", Cmp("!=", Col("d"), Lit(3)),
                             Cmp("<", Col("d"), Lit(20))),
                      (d != 3) & (d < 20)),
        "two_columns": (BoolOp("and", Cmp(">=", Col("d"), Col("v")),
                               Cmp("<", Col("d"), Lit(10))),
                        (d >= v) & (d < 10)),
        "or": (BoolOp("or", Cmp(">=", Col("d"), Lit(398)),
                      Cmp("<", Col("d"), Lit(2))), (d >= 398) | (d < 2)),
        "float_bound": (BoolOp("and", Cmp(">=", Col("d"), Lit(10.5)),
                               Cmp("<", Col("d"), Lit(20))),
                        (d >= 10.5) & (d < 20)),
        "out_of_int32": (BoolOp("and", Cmp(">=", Col("d"), Lit(-2**40)),
                                Cmp("<", Col("d"), Lit(3))), d < 3),
    }[pred]
    if pred == "out_of_int32":
        assert algebra._range_selection(
            p, db.table("t"), None) is None
        return
    before = _counts()
    width = _check_all_paths(monkeypatch, db, data, p, None, want)
    assert width is None
    assert _counts()[0] == before[0]
    assert _counts()[1] > before[1]


@pytest.mark.parametrize("extra", [0, 1])
def test_the_index_serves_at_most_an_eighth_of_the_rows(monkeypatch, extra):
    """A window of exactly nrows / 8 rows takes the index, one row more
    takes the full columns."""
    n = 1600
    d = np.repeat(np.arange(n // 4), 4)      # four rows a value
    rng = np.random.default_rng(SEED + 3)
    data = _data(rng, n=n)
    data["t"]["d"] = rng.permutation(d)
    db = _db(data)
    lo = 37
    hi = lo + n // 8 // 4 + extra            # 200 or 204 rows
    want = _numpy_range(data["t"]["d"], lo, hi)
    assert want.sum() == n // 8 + 4 * extra
    width = _check_all_paths(monkeypatch, db, data, _range(),
                             {"lo": lo, "hi": hi}, want)
    assert width == (256 if extra == 0 else None)


@pytest.mark.parametrize("n_rows", [63, 64, 65, 127, 128, 129])
def test_counts_that_straddle_a_bucket(monkeypatch, n_rows):
    """The selection is the least power of two that holds the range."""
    rng = np.random.default_rng(SEED + 4)
    data = _data(rng)
    d = np.arange(N) // 1           # one row a value
    data["t"]["d"] = rng.permutation(d)
    db = _db(data)
    lo = 900
    want = _numpy_range(data["t"]["d"], lo, lo + n_rows)
    width = _check_all_paths(monkeypatch, db, data, _range(),
                             {"lo": lo, "hi": lo + n_rows}, want)
    assert width == 1 << (n_rows - 1).bit_length()


def test_a_new_data_epoch_builds_a_new_index(monkeypatch):
    rng = np.random.default_rng(SEED + 5)
    data = _data(rng)
    db = _db(data)
    params = {"lo": 10, "hi": 40}
    old = db.table("t").column("d")
    _check_all_paths(monkeypatch, db, data, _range(), params,
                     _numpy_range(data["t"]["d"], 10, 40))
    assert algebra._RANGE_INDEX.get(old) is not None

    data["t"]["d"] = rng.integers(0, 400, N)
    db.replace_table(_table("t", **data["t"]))
    new = db.table("t").column("d")
    assert algebra._RANGE_INDEX.get(new) is None
    _check_all_paths(monkeypatch, db, data, _range(), params,
                     _numpy_range(data["t"]["d"], 10, 40))
    assert algebra._RANGE_INDEX.get(new) is not None


def test_the_counters_count_each_masked_select_once():
    """One index select for the range over ``t``, one full select for the
    semi-join's build side, none for an unmasked query."""
    rng = np.random.default_rng(SEED + 6)
    db = _db(_data(rng))
    params = {"lo": 10, "hi": 40}
    before = _counts()
    algebra._BUILDS.clear()
    db.run(_grouped(_range()), params)
    assert tuple(a - b for a, b in zip(_counts(), before)) == (1, 1)
    # the build side is kept per data epoch: only the range runs again
    db.run(_grouped(_range()), params)
    assert tuple(a - b for a, b in zip(_counts(), before)) == (2, 1)
    Select(_range(), Scan("t")).execute(db, params)
    assert tuple(a - b for a, b in zip(_counts(), before)) == (2, 1)


@pytest.mark.parametrize("inner", ["range", "other"])
def test_chained_selects(monkeypatch, inner):
    """A σ over a range selection reads the selected rows; a range over a
    σ evaluated on full columns takes that σ's mask at the selected
    rows."""
    rng = np.random.default_rng(SEED + 7)
    data = _data(rng)
    db = _db(data)
    other = Cmp("<", Col("v"), Lit(10))
    inner_pred, outer_pred = (_range(), other) if inner == "range" \
        else (other, _range())
    query = Select(outer_pred, Select(inner_pred, Scan("t")))
    params = {"lo": 200, "hi": 230}
    t = data["t"]
    want = (t["v"] < 10) & _numpy_range(t["d"], 200, 230)
    before = _counts()
    (ids, width), (full_ids, full_width) = _both(
        monkeypatch, lambda: _kept(db, query, params))
    assert ids.tolist() == full_ids.tolist() == np.flatnonzero(want).tolist()
    assert width is not None and full_width is None
    # each path evaluated two σ: index then index, or full then index
    index, full = (a - b for a, b in zip(_counts(), before))
    assert (index, full) == ((2, 2) if inner == "range" else (1, 3))


@pytest.mark.parametrize("keys", ["direct", "sorted"])
def test_a_range_on_the_build_side(monkeypatch, keys):
    """The right side of a semi-join selected by a range: its keys taken
    at the selected rows, for the direct-address build (kept per data
    epoch only without parameters, so built again here) and for the sort
    and search (negative keys)."""
    rng = np.random.default_rng(SEED + 8)
    data = _data(rng)
    if keys == "sorted":
        data["t"]["k"] = data["t"]["k"] - 100
        data["r"]["rk"] = data["r"]["rk"] - 100
    data["r"]["rd"] = rng.integers(0, 400, data["r"]["rk"].size)
    db = _db(data)
    semi = SemiJoin(Select(Cmp(">", Col("v"), Lit(0)), Scan("t")),
                    Select(BoolOp("and", Cmp(">=", Col("rd"), Param("lo")),
                                  Cmp("<=", Col("rd"), Param("hi"))),
                           Scan("r")), "k", "rk")
    params = {"lo": 50, "hi": 70}
    t, r = data["t"], data["r"]
    build = r["rk"][(r["rd"] >= 50) & (r["rd"] <= 70)]
    want = t["k"][(t["v"] > 0) & np.isin(t["k"], build)].tolist()
    before = _counts()
    got, full = _both(monkeypatch, lambda: semi.execute(db, params))
    assert _table_values(got)["k"] == _table_values(full)["k"] == want
    # the sort and search evaluates the right side again after the
    # direct-address build refused its keys
    assert _counts()[0] - before[0] == (1 if keys == "direct" else 2)


INT32 = np.iinfo(np.int32)


def _int32_data(rng):
    """``d`` over the whole int32 range, with rows at both of its edges
    and next to them, and duplicates."""
    data = _data(rng)
    edges = [INT32.min] * 3 + [INT32.min + 1, INT32.max - 1] + [INT32.max] * 3
    d = rng.integers(INT32.min, INT32.max, N, endpoint=True)
    d[:len(edges)] = edges
    d[len(edges):len(edges) + 40] = 12345
    data["t"]["d"] = rng.permutation(d).astype(np.int32)
    return data


def _parent_selection(values, low, high, nrows):
    """The selection's offsets as the bounds searched over the sorted
    values widened to int64, where every int32 bound is exact."""
    wide = values.astype(np.int64)
    lo = int(np.searchsorted(wide, low, "left"))
    n = max(int(np.searchsorted(wide, high, "right")) - lo, 0)
    if n > nrows / 8:
        return None
    width = 1 << max(n - 1, 0).bit_length()
    start = min(lo, nrows - width)
    return start, lo - start, lo - start + n, width


# (lo, hi) of ``d >= lo and d <= hi``: at the dtype's edges, one past the
# far edge on each side (an empty range), inverted, and the whole dtype
# (more than an eighth: full columns)
INT32_WINDOWS = {"at_min": (INT32.min, INT32.min),
                 "near_min": (INT32.min, INT32.min + 1),
                 "at_max": (INT32.max, INT32.max),
                 "near_max": (INT32.max - 1, INT32.max),
                 "low_past_max": (INT32.max + 1, INT32.max),
                 "high_past_min": (INT32.min, INT32.min - 1),
                 "duplicates": (12345, 12345),
                 "inverted": (100, -100),
                 "whole_dtype": (INT32.min, INT32.max)}


@pytest.mark.parametrize("window", sorted(INT32_WINDOWS))
def test_int32_bounds_select_alike_in_every_integer_type(window):
    """Python-int, np.int64 and (where it holds the bound) np.int32 bounds
    give one selection, the one the bounds searched in int64 give, and it
    keeps exactly the rows numpy's mask keeps; bounds past the dtype's far
    edge give an empty range and raise nothing."""
    rng = np.random.default_rng(SEED + 9)
    data = _int32_data(rng)
    db = _db(data)
    base = db.table("t")
    lo, hi = INT32_WINDOWS[window]
    kinds = [int, np.int64]
    if INT32.min <= lo <= INT32.max and INT32.min <= hi <= INT32.max:
        kinds.append(np.int32)
    want = _numpy_range(data["t"]["d"].astype(np.int64), lo, hi, "ge", "le")
    pred = _range("ge", "le")
    got = []
    for kind in kinds:
        params = {"lo": kind(lo), "hi": kind(hi)}
        sel = algebra._range_selection(pred, base, params)
        got.append(None if sel is None
                   else (sel.start, sel.lo, sel.hi, sel.width))
        ids, _ = _kept(db, Select(pred, Scan("t")), params)
        assert ids.tolist() == np.flatnonzero(want).tolist()
    values = algebra._RANGE_INDEX(base.column("d")).values
    assert got == [_parent_selection(values, lo, hi, N)] * len(kinds)
    if sel is not None:
        assert sel.hi - sel.lo == int(want.sum())
    assert (sel is None) == (window == "whole_dtype")


@pytest.mark.parametrize("kind", [int, np.int64, np.int32])
def test_the_bounds_are_searched_in_the_columns_dtype(monkeypatch, kind):
    """Each key the index's search receives is of the sorted values' own
    dtype, so numpy neither promotes nor copies the column a request."""
    rng = np.random.default_rng(SEED + 10)
    data = _int32_data(rng)
    db = _db(data)
    base = db.table("t")
    values = algebra._RANGE_INDEX(base.column("d")).values
    keys = []
    search = np.searchsorted

    def recording(a, v, *args, **kwargs):
        if a is values:
            keys.append(v)
        return search(a, v, *args, **kwargs)

    monkeypatch.setattr(algebra.np, "searchsorted", recording)
    for lo, hi in [(12345, 12345), (INT32.min, INT32.min + 1),
                   (INT32.max - 1, INT32.max)]:
        sel = algebra._range_selection(_range("ge", "le"), base,
                                       {"lo": kind(lo), "hi": kind(hi)})
        assert sel is not None
    assert len(keys) == 6
    for k in keys:
        assert isinstance(k, np.generic) and k.dtype == values.dtype, k
