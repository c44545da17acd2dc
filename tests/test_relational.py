"""Relational substrate: tables, algebra execution, estimates, client env."""

import numpy as np
import pytest

from repro.relational import (AggSpec, Aggregate, ClientEnv, Cmp, Col,
                              DatabaseServer, FAST_LOCAL, Field, Join, Lit,
                              OrderBy, Project, Scan, Schema, Select,
                              SLOW_REMOTE, Table, equi_join_indices)


@pytest.fixture
def db():
    rng = np.random.default_rng(0)
    cust = Table.from_columns(
        "customer",
        Schema.of(Field("c_id", "int64", 8), Field("c_year", "int32", 4),
                  Field("c_pay", "int32", 120)),
        c_id=np.arange(100), c_year=rng.integers(1940, 2000, 100),
        c_pay=rng.integers(0, 10, 100))
    orders = Table.from_columns(
        "orders",
        Schema.of(Field("o_id", "int64", 8), Field("o_cid", "int64", 8),
                  Field("o_amt", "float64", 8)),
        o_id=np.arange(500), o_cid=rng.integers(0, 100, 500),
        o_amt=rng.uniform(0, 1000, 500))
    return DatabaseServer({"customer": cust, "orders": orders})


def test_row_bytes_uses_wire_sizes(db):
    assert db.table("customer").row_bytes == 8 + 4 + 120


def test_select_matches_numpy(db):
    t = Select(Cmp("<", Col("c_year"), Lit(1960)), Scan("customer")).execute(db)
    want = int((np.asarray(db.table("customer").column("c_year")) < 1960).sum())
    assert t.nrows == want


def test_join_row_count_and_order(db):
    res = Join(Scan("orders"), Scan("customer"), "o_cid", "c_id").execute(db)
    assert res.nrows == 500  # FK integrity: every order matches one customer
    # left-major order preserved
    assert np.array_equal(np.asarray(res.column("o_id")), np.arange(500))


def test_equi_join_indices_all_pairs():
    lk = np.array([1, 2, 2, 3])
    rk = np.array([2, 2, 3, 9])
    li, ri = equi_join_indices(lk, rk)
    pairs = set(zip(li.tolist(), ri.tolist()))
    assert pairs == {(1, 0), (1, 1), (2, 0), (2, 1), (3, 2)}


def test_groupby_sum_matches_numpy(db):
    res = Aggregate(("o_cid",), (AggSpec("sum", "o_amt", "s"),
                                 AggSpec("count", None, "n")),
                    Scan("orders")).execute(db)
    a = np.asarray(db.table("orders").column("o_cid"))
    b = np.asarray(db.table("orders").column("o_amt"))
    for k, s, n in zip(np.asarray(res.column("o_cid")),
                       np.asarray(res.column("s")),
                       np.asarray(res.column("n"))):
        sel = b[a == k]
        assert abs(float(s) - sel.sum()) < 1e-2 * max(1.0, abs(sel.sum()))
        assert int(n) == len(sel)


def test_orderby_sorted(db):
    res = OrderBy(("c_year",), Scan("customer")).execute(db)
    ys = np.asarray(res.column("c_year"))
    assert np.all(ys[:-1] <= ys[1:])


def test_estimates_reasonable(db):
    est = db.estimate(Scan("orders"))
    assert est.n_rows == 500
    est = db.estimate(Select(Cmp("==", Col("o_cid"), Lit(5)), Scan("orders")))
    assert 1 <= est.n_rows <= 20  # 500/NDV(100) = 5
    est = db.estimate(Join(Scan("orders"), Scan("customer"), "o_cid", "c_id"))
    assert 250 <= est.n_rows <= 1000


def test_client_env_charges_query_cost(db):
    env = ClientEnv(db, SLOW_REMOTE)
    t = env.execute_query(Scan("customer"))
    expected_transfer = t.nrows * t.row_bytes / SLOW_REMOTE.bandwidth_bytes_per_s
    assert env.clock >= SLOW_REMOTE.rtt_s + expected_transfer
    assert env.n_queries == 1


def test_orm_cache_hit_is_local(db):
    env = ClientEnv(db, SLOW_REMOTE)
    env.point_lookup("customer", "c_id", 7)
    q1, t1 = env.n_queries, env.clock
    env.point_lookup("customer", "c_id", 7)
    assert env.n_queries == q1            # cache hit: no extra round trip
    assert env.clock - t1 < 1e-6


def test_prefetch_cache_lookup(db):
    env = ClientEnv(db, FAST_LOCAL)
    env.cache_by_column(db.table("customer"), "c_id")
    row = env.lookup_cache("customer", "c_id", 42)
    assert row["c_id"] == 42
    assert env.lookup_cache("customer", "c_id", 10**9) is None


# --------------------------------------------------------------------------
# The prefetch cache's host image: index built once, rows read on the host
# --------------------------------------------------------------------------

def _tasks(keys=(3, 1, 3, 2, 3, 1), name="tasks"):
    n = len(keys)
    return Table.from_columns(
        name, Schema.of(Field("t_role", "int32"), Field("t_id", "int32"),
                        Field("t_hours", "float32")),
        t_role=np.asarray(keys), t_id=np.arange(n) * 10,
        t_hours=np.linspace(0.1, 2.3, n))


def _client_delta(before):
    from repro.relational.database import CLIENT
    return CLIENT.diff(before)


@pytest.mark.parametrize("rewrap", [False, True])
def test_prefetch_index_is_built_once_per_table(rewrap):
    from repro.relational.database import CLIENT
    t = _tasks()
    env = ClientEnv(DatabaseServer({"tasks": t}), FAST_LOCAL)
    before = CLIENT.snapshot()
    env.cache_by_column(t, "t_role")
    again = Table("tasks_by_role", t.schema, t.columns) if rewrap else t
    env.cache_by_column(again, "t_role")
    assert _client_delta(before) == {"index_builds": 1, "index_reuses": 1}
    assert len(env.lookup_cache_all(again.name, "t_role", 3)) == 3


@pytest.mark.parametrize("lookup", ["lookup_cache", "lookup_cache_all"])
def test_lookups_after_the_first_read_nothing_from_the_device(lookup):
    from repro.obs.transfer import TRANSFERS
    t = _tasks()
    db = DatabaseServer({"tasks": t})
    env = ClientEnv(db, FAST_LOCAL)
    env.cache_by_column(t, "t_role")
    assert env.lookup_cache_all("tasks", "t_role", 2)    # pulls the columns
    before = TRANSFERS.snapshot()
    for key in (1, 2, 3, 7):
        getattr(env, lookup)("tasks", "t_role", key)
    # a later request re-prefetches the same result: no pull either
    env = ClientEnv(db, FAST_LOCAL)
    env.cache_by_column(t, "t_role")
    getattr(env, lookup)("tasks", "t_role", 3)
    assert TRANSFERS.diff(before) == {}


@pytest.mark.parametrize("page", [1, 2, 4, 6])
def test_host_image_pulls_each_page_once_at_its_first_read(monkeypatch, page):
    from repro.obs.transfer import TRANSFERS
    from repro.relational import database
    monkeypatch.setattr(database, "_PAGE_ROWS", page)
    t = _tasks(keys=(3, 1, 3, 2, 3, 1))     # in key order: 1 1 2 3 3 3
    env = ClientEnv(DatabaseServer({"tasks": t}), FAST_LOCAL)
    env.cache_by_column(t, "t_role")
    sorted_at = {1: range(0, 2), 2: range(2, 3), 3: range(3, 6), 7: range(0)}
    pulled = set()
    for key in (1, 1, 3, 2, 7, 3):
        before = TRANSFERS.snapshot()
        rows = env.lookup_cache_all("tasks", "t_role", key)
        assert rows == [r for r in t.to_rows() if r["t_role"] == key]
        pages = {i // page for i in sorted_at[key]} - pulled
        pulled |= pages
        reads = TRANSFERS.diff(before).get(
            "host_reads{site=database.lookup_cache}", 0)
        assert reads == 3 * len(pages)      # one read a column a page


@pytest.mark.parametrize("key,want", [
    (3, 3), (np.int64(1), 1), (np.int64(2**32 + 3), None), (2**40, None),
    (7, None), (3.0, 3), (3.5, None)])
def test_cached_rows_are_table_rows(key, want):
    t = _tasks()
    env = ClientEnv(DatabaseServer({"tasks": t}), FAST_LOCAL)
    env.cache_by_column(t, "t_role")
    stored = np.asarray(t.column("t_role"))
    rows = [] if want is None else [t.row(int(i))
                                    for i in np.flatnonzero(stored == want)]

    def typed(rs):
        return [[(k, type(v), v) for k, v in r.items()] for r in rs]

    got = env.lookup_cache_all("tasks", "t_role", key)
    assert typed(got) == typed(rows)
    one = env.lookup_cache("tasks", "t_role", key)
    assert typed([] if one is None else [one]) == typed(rows[:1])


@pytest.mark.parametrize("same_keys", [False, True])
def test_fresh_prefetch_after_replace_table_reads_new_values(same_keys):
    t = _tasks()
    db = DatabaseServer({"tasks": t})
    env = ClientEnv(db, FAST_LOCAL)
    env.cache_by_column(db.table("tasks"), "t_role")
    assert [r["t_id"] for r in env.lookup_cache_all("tasks", "t_role", 3)] \
        == [0, 20, 40]
    if same_keys:       # the key column's array is shared with the old table
        new = t.with_column(t.schema.field("t_id"), np.arange(6) + 100)
    else:
        new = _tasks(keys=(3, 3, 1, 1, 2, 2))
    db.replace_table(new)
    env = ClientEnv(db, FAST_LOCAL)
    env.cache_by_column(db.table("tasks"), "t_role")
    want = [new.row(i) for i in range(new.nrows) if new.row(i)["t_role"] == 3]
    assert env.lookup_cache_all("tasks", "t_role", 3) == want
    assert env.lookup_cache("tasks", "t_role", 3) == want[0]


def test_project_computed_column(db):
    from repro.relational import Arith
    q = Project(("o_id",), Scan("orders"), computed=(("dbl", Arith("*", Col("o_amt"), Lit(2.0))),))
    t = q.execute(db)
    assert np.allclose(np.asarray(t.column("dbl")),
                       2 * np.asarray(db.table("orders").column("o_amt")), rtol=1e-5)


def test_table_semantic_equality(db):
    t = db.table("customer")
    shuffled = t.take(np.random.default_rng(3).permutation(t.nrows))
    assert t.same_rows(shuffled)
    assert not t.same_rows(shuffled, ordered=True) or t.nrows <= 1
