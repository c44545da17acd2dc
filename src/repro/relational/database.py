"""Simulated client/server database environment.

The paper evaluates Cobra against a real MySQL server over ethernet with a
network simulator (Sec. VIII). This container has neither, so we model the
*same knobs the paper's cost catalog exposes*:

  C_NRT       network round-trip time
  BW          network bandwidth
  C_Q^F/C_Q^L server time to first/last row (from a simple server model —
              the paper "consulted the database query optimizer" for these)
  C_Z         per-imperative-statement cost
  AF_Q        amortization factor for prefetched queries

Two distinct views (kept deliberately separate):

  * ``DatabaseServer.run(query)``      — actually executes (jnp compute) and
    returns TRUE timing from true cardinalities → the *simulated wall clock*
    ("actual running time" axis of Fig. 13).
  * ``DatabaseServer.estimate(query)`` — cardinality/cost ESTIMATES from table
    statistics only → what Cobra's cost model consumes.

``ClientEnv`` owns the simulated clock, the ORM id-cache (Hibernate caches
fetched rows by primary key — needed to reproduce Fig. 13b), and the
client-side prefetch cache (``cacheByColumn`` / ``lookup``, footnote 3).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import numpy as np

from ..obs.metrics import MetricsRegistry
from ..obs.trace import NOOP_TRACER
from ..obs.transfer import to_device, to_host
from .algebra import (Aggregate, Join, Limit, OrderBy, Project, Query, Scan,
                      Select, SemiJoin, resolve_rows, search_key)
from .memo import TableMemo
from .table import Table

__all__ = [
    "NetworkProfile", "ServerModel", "TableStats", "QueryEstimate",
    "DatabaseServer", "ClientEnv", "SLOW_REMOTE", "FAST_LOCAL",
]


# --------------------------------------------------------------------------
# Environment profiles (paper Sec. VIII, Experiment 1/2 settings)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NetworkProfile:
    name: str
    bandwidth_bytes_per_s: float
    rtt_s: float

    @property
    def c_nrt(self) -> float:
        return self.rtt_s


# bandwidth 500 kbps, latency 250 ms  (paper: "slow remote network")
SLOW_REMOTE = NetworkProfile("slow_remote", bandwidth_bytes_per_s=500e3 / 8, rtt_s=0.250)
# bandwidth 6 gbps, rtt 0.5 ms        (paper: "fast local network")
FAST_LOCAL = NetworkProfile("fast_local", bandwidth_bytes_per_s=6e9 / 8, rtt_s=0.5e-3)


@dataclasses.dataclass(frozen=True)
class ServerModel:
    """A simple DB-server timing model (stand-in for 'consult the optimizer').

    All rates in rows/second; overheads in seconds. Values loosely calibrated
    to a MySQL 5.7-class server on the paper's hardware.
    """

    startup_s: float = 2e-4            # parse/plan/dispatch per query
    scan_rows_per_s: float = 8e6       # sequential scan emit rate
    index_lookup_s: float = 3e-5       # one B-tree point lookup
    hash_build_rows_per_s: float = 6e6
    hash_probe_rows_per_s: float = 7e6
    sort_rows_per_s: float = 2.5e6     # n log n folded into effective rate
    agg_rows_per_s: float = 9e6
    emit_rows_per_s: float = 1.2e7     # result serialization


@dataclasses.dataclass(frozen=True)
class TableStats:
    nrows: int
    row_bytes: int
    distinct: Mapping[str, int]        # per-column NDV
    minmax: Mapping[str, Tuple[float, float]]
    # per-column histograms (repro.stats.histogram) — empty when the
    # server was built with StatsConfig(histograms=False); their reprs
    # carry content digests, so stats_fingerprint() content-addresses
    # them through repr(TableStats) unchanged
    hists: Mapping[str, "object"] = dataclasses.field(default_factory=dict)

    def ndv(self, col: str) -> int:
        return max(1, int(self.distinct.get(col, max(1, self.nrows // 10))))

    def hist(self, col: str):
        """The column's :class:`~repro.stats.histogram.ColumnHistogram`,
        or None (no histogram statistics for it)."""
        return self.hists.get(col)


@dataclasses.dataclass(frozen=True)
class QueryEstimate:
    """What the optimizer knows about a query before running it (Fig. 12 terms)."""

    n_rows: float          # N_Q
    row_bytes: float       # S_row(Q)
    first_row_s: float     # C_Q^F
    last_row_s: float      # C_Q^L

    @property
    def result_bytes(self) -> float:
        return self.n_rows * self.row_bytes


# --------------------------------------------------------------------------
# Server
# --------------------------------------------------------------------------

_INSTANCE_TOKENS = itertools.count(1)


class DatabaseServer:
    def __init__(self, tables: Dict[str, Table], model: ServerModel = ServerModel(),
                 stats_config=None, tracer=None):
        from ..stats.histogram import DEFAULT_STATS_CONFIG
        self.tables = dict(tables)
        self.model = model
        # spans of query execution and ANALYZE (``server.run``,
        # ``server.analyze``); a session adopts a server without a tracer
        self.tracer = tracer if tracer is not None else NOOP_TRACER
        self.stats_config = stats_config if stats_config is not None \
            else DEFAULT_STATS_CONFIG
        # process-unique identity: result caches shared across sessions key
        # on it so two servers' identically-named tables never collide
        self.instance_token = next(_INSTANCE_TOKENS)
        self._stats: Dict[str, TableStats] = {}
        self._stats_version = 0
        self._table_versions: Dict[str, int] = {}
        self._data_versions: Dict[str, int] = {}
        # per-column histogram builds since startup — the ANALYZE work
        # counter targeted re-analyzes are judged by (tests/bench)
        self.histogram_builds = 0
        # the rows each node of the running query produced, noted by the
        # operators (``algebra.record_rows``): a count, or a row mask or
        # device count not yet read; None outside ``run``
        self._run_rows: Optional[Dict[int, object]] = None
        self.analyze()

    def table(self, name: str) -> Table:
        return self.tables[name]

    def add_table(self, t: Table) -> None:
        """Install (or replace) a table AND refresh its statistics."""
        self.tables[t.name] = t
        self._stats[t.name] = self._compute_stats(t)
        self._stats_version += 1
        self._table_versions[t.name] = self._table_versions.get(t.name, 0) + 1
        self._data_versions[t.name] = self._data_versions.get(t.name, 0) + 1

    def replace_table(self, t: Table) -> None:
        """Replace a table's DATA without refreshing statistics — like a bulk
        load on a real server before anyone runs ANALYZE. Estimates go stale
        (``estimate()`` keeps consulting the old stats) while ``run()`` sees
        the new rows; the serving runtime's feedback controller exists to
        detect exactly this drift and trigger a re-analyze. The table's DATA
        version does bump (result caches must never serve the old rows)."""
        self.tables[t.name] = t
        self._data_versions[t.name] = self._data_versions.get(t.name, 0) + 1

    # ----------------------------------------------------------- statistics
    @property
    def stats_version(self) -> int:
        """Monotonic counter over statistics refreshes. Any change to the
        stats a cost model may have consumed (``analyze()``, table
        replacement) bumps it; plan caches key on it for invalidation."""
        return self._stats_version

    def table_version(self, name: str) -> int:
        """Per-table stats version. Plan caches key compiled programs on the
        versions of only the tables they touch, so refreshing an unrelated
        table's statistics leaves those plans hot."""
        return self._table_versions.get(name, 0)

    def data_version(self, name: str) -> int:
        """Per-table DATA version: bumps whenever a table's rows change
        (``add_table``, ``replace_table``, interpreter updates), whether or
        not statistics were refreshed. Result caches — the serving-level
        :class:`~repro.runtime.sitecache.SiteCache` — key on it so a cached
        query result is never served over rows it was not computed from."""
        return self._data_versions.get(name, 0)

    def stats_token(self, tables) -> Tuple[Tuple[str, int], ...]:
        """Cache-key component: (table, stats version) for each named table."""
        return tuple((t, self.table_version(t)) for t in sorted(set(tables)))

    def site_epoch(self, tables) -> Tuple[Tuple[str, int, int], ...]:
        """Result-cache validity token: (table, stats version, data version)
        per named table. Any ``analyze()`` or write to one of the tables
        changes the epoch, so epoch-keyed cached results self-invalidate."""
        return tuple((t, self.table_version(t), self.data_version(t))
                     for t in sorted(set(tables)))

    def stats_fingerprint(self, tables) -> Tuple[Tuple[str, str], ...]:
        """CONTENT hash of the named tables' current statistics.

        Version counters are process-local (a restarted server re-analyzes
        from zero), so the cross-session plan store compares this instead:
        a stored plan stays warm across restarts as long as the statistics
        it was costed on are byte-equal, regardless of how many ``analyze()``
        calls either process has issued."""
        import hashlib
        out = []
        for t in sorted(set(tables)):
            st = self._stats.get(t)
            digest = ("missing" if st is None else
                      hashlib.sha256(repr(st).encode()).hexdigest()[:16])
            out.append((t, digest))
        return tuple(out)

    def analyze(self, *tables: str,
                columns: Optional[Tuple[str, ...]] = None) -> int:
        """Refresh table statistics. With no arguments every table is
        re-analyzed (the legacy behaviour); naming tables refreshes only
        those, bumping only their per-table versions. ``columns`` makes
        the refresh *targeted*: scalar statistics (row counts, NDV,
        min/max) always recompute, but histograms rebuild only for the
        named columns — the others carry over from the previous stats —
        which is what the feedback controller's q-error path requests
        when one site's estimate went bad."""
        names = tables or tuple(self.tables)
        with self.tracer.span("server.analyze", tables=names):
            for name in names:
                self._stats[name] = self._compute_stats(
                    self.tables[name], columns=columns,
                    prev=self._stats.get(name) if columns else None)
                self._table_versions[name] = \
                    self._table_versions.get(name, 0) + 1
        self._stats_version += 1
        return self._stats_version

    def _compute_stats(self, t: Table,
                       columns: Optional[Tuple[str, ...]] = None,
                       prev: Optional[TableStats] = None) -> TableStats:
        from ..stats.histogram import build_histogram
        distinct, minmax, hists = {}, {}, {}
        want = None if columns is None else set(columns)
        for f in t.schema.fields:
            arr = to_host(t.column(f.name), "database.analyze")
            if arr.size:
                distinct[f.name] = int(len(np.unique(arr)))
                minmax[f.name] = (float(arr.min()), float(arr.max()))
            else:
                distinct[f.name] = 1
                minmax[f.name] = (0.0, 0.0)
            if not self.stats_config.histograms:
                continue
            if want is not None and f.name not in want:
                # targeted analyze: keep the previous histogram (possibly
                # stale — exactly the staleness the q-error signal scores)
                carried = prev.hist(f.name) if prev is not None else None
                if carried is not None:
                    hists[f.name] = carried
                continue
            hists[f.name] = build_histogram(arr, self.stats_config)
            self.histogram_builds += 1
        return TableStats(t.nrows, t.row_bytes, distinct, minmax, hists)

    def stats(self, name: str) -> TableStats:
        return self._stats[name]

    # ----------------------------------------------------------- execution
    def run(self, query: Query, params: Optional[Mapping[str, object]] = None
            ) -> Tuple[Table, float, float]:
        """Execute and return (result, true C_Q^F, true C_Q^L)."""
        tracer = self.tracer
        with tracer.span("server.run") as sp:
            if tracer.enabled:
                sp.attrs["sql"] = query.sql()
            self._run_rows = {}
            try:
                result = query.execute(self, params)
                first, last = self._true_times(query, params)
            finally:
                self._run_rows = None
        return result, first, last

    def _rows_of(self, params) -> Callable[[Query], int]:
        """The true row count of a node of the running query: the one its
        operator noted (device counts and masks read in one pull), else
        from running the node again."""
        rec = self._run_rows or {}
        rec.update(zip(rec, resolve_rows(rec.values())))

        def rows(node: Query) -> int:
            n = rec.get(id(node))
            return n if n is not None else node.execute(self, params).nrows
        return rows

    def _true_times(self, q: Query, params) -> Tuple[float, float]:
        """Server time model evaluated on TRUE cardinalities (post-execution)."""
        m = self.model
        total = m.startup_s
        blocking = m.startup_s
        rows = self._rows_of(params)

        def walk(node: Query) -> int:
            nonlocal total, blocking
            if isinstance(node, Scan):
                n = self.table(node.table).nrows
                total += n / m.scan_rows_per_s
                return n
            if isinstance(node, Select):
                walk(node.child)
                return rows(node)
            if isinstance(node, Project):
                return walk(node.child)
            if isinstance(node, Join):
                nl = walk(node.left)
                nr = walk(node.right)
                build = min(nl, nr)
                probe = max(nl, nr)
                total += build / m.hash_build_rows_per_s + probe / m.hash_probe_rows_per_s
                blocking += build / m.hash_build_rows_per_s
                return rows(node)
            if isinstance(node, SemiJoin):
                # the right side is the hash table built, the left probes it
                probe = walk(node.left)
                build = walk(node.right)
                total += build / m.hash_build_rows_per_s + probe / m.hash_probe_rows_per_s
                blocking += build / m.hash_build_rows_per_s
                return rows(node)
            if isinstance(node, Aggregate):
                n_in = walk(node.child)
                total += n_in / m.agg_rows_per_s
                blocking = total  # aggregation is blocking
                return rows(node)
            if isinstance(node, OrderBy):
                n_in = walk(node.child)
                total += n_in / m.sort_rows_per_s
                blocking = total  # sort is blocking
                return n_in
            if isinstance(node, Limit):
                return min(node.k, walk(node.child))
            raise TypeError(f"unknown node {node}")

        n_out = walk(q)
        total += n_out / m.emit_rows_per_s
        first = min(blocking, total)
        last = total
        return first, last

    # ----------------------------------------------------------- estimation
    def estimate(self, q: Query, params_known: bool = False) -> QueryEstimate:
        """Cardinality + server-time estimates from statistics only."""
        m = self.model
        total = m.startup_s
        blocking = m.startup_s

        def est_rows(node: Query) -> Tuple[float, float]:
            """returns (est rows, est row_bytes)"""
            nonlocal total, blocking
            if isinstance(node, Scan):
                st = self.stats(node.table)
                total += st.nrows / m.scan_rows_per_s
                return float(st.nrows), float(st.row_bytes)
            if isinstance(node, Select):
                n, rb = est_rows(node.child)
                sel = self._selectivity(node)
                return max(1.0, n * sel), rb
            if isinstance(node, Project):
                n, rb = est_rows(node.child)
                try:
                    rb_exact = float(node.output_schema(self).row_bytes)
                    return n, max(4.0, rb_exact)
                except Exception:
                    sch_cols = len(node.cols) + len(node.computed)
                    return n, max(4.0, rb * sch_cols / max(1, sch_cols + 2))
            if isinstance(node, Join):
                nl, rbl = est_rows(node.left)
                nr, rbr = est_rows(node.right)
                ndv_l = self._ndv_of(node.left, node.left_key)
                ndv_r = self._ndv_of(node.right, node.right_key)
                out = nl * nr / max(ndv_l, ndv_r, 1.0)
                build = min(nl, nr)
                probe = max(nl, nr)
                total += build / m.hash_build_rows_per_s + probe / m.hash_probe_rows_per_s
                blocking += build / m.hash_build_rows_per_s
                return max(1.0, out), rbl + rbr
            if isinstance(node, SemiJoin):
                # a left row survives when its key is among the right's
                # distinct keys: their share of the left key's distinct
                # values, under containment
                nl, rbl = est_rows(node.left)
                nr, _ = est_rows(node.right)
                ndv_l = self._ndv_of(node.left, node.left_key)
                ndv_r = min(self._ndv_of(node.right, node.right_key), nr)
                total += nr / m.hash_build_rows_per_s + nl / m.hash_probe_rows_per_s
                blocking += nr / m.hash_build_rows_per_s
                return max(1.0, nl * min(1.0, ndv_r / max(ndv_l, 1.0))), rbl
            if isinstance(node, Aggregate):
                n, rb = est_rows(node.child)
                total += n / m.agg_rows_per_s
                blocking = total
                if not node.group_by:
                    return 1.0, 8.0 * len(node.aggs)
                groups = 1.0
                for g in node.group_by:
                    groups *= self._ndv_of(node.child, g)
                return min(n, groups), 8.0 * (len(node.group_by) + len(node.aggs))
            if isinstance(node, OrderBy):
                n, rb = est_rows(node.child)
                total += n / m.sort_rows_per_s
                blocking = total
                return n, rb
            if isinstance(node, Limit):
                n, rb = est_rows(node.child)
                return min(float(node.k), n), rb
            raise TypeError(f"unknown node {node}")

        n, rb = est_rows(q)
        total += n / m.emit_rows_per_s
        return QueryEstimate(n_rows=n, row_bytes=rb,
                             first_row_s=min(blocking, total), last_row_s=total)

    def _selectivity(self, node: Select) -> float:
        from ..stats.selectivity import predicate_selectivity
        sel = predicate_selectivity(
            node.pred,
            resolve=lambda col: self._hist_of(node.child, col),
            ndv_of=lambda col: self._ndv_of(node.child, col))
        return 0.5 if sel is None else sel

    def _hist_of(self, node: Query, col: str):
        """The column's histogram at the Select's input, resolved like
        ``_ndv_of``: walk row-preserving nodes down to the base Scan. Join
        and post-aggregate inputs return None (their output distribution
        is not a base column's), falling back to the scalar estimates."""
        if isinstance(node, Scan):
            st = self._stats.get(node.table)
            return st.hist(col) if st is not None else None
        if isinstance(node, (Select, Project, OrderBy, Limit, SemiJoin)):
            kids = node.children()
            return self._hist_of(kids[0], col) if kids else None
        return None

    def _ndv_of(self, node: Query, col: str) -> float:
        if isinstance(node, Scan):
            return float(self.stats(node.table).ndv(col))
        if isinstance(node, (Select, Project, OrderBy, Limit, Aggregate,
                             SemiJoin)):
            kids = node.children()
            return self._ndv_of(kids[0], col) if kids else 100.0
        if isinstance(node, Join):
            try:
                return self._ndv_of(node.left, col)
            except Exception:
                return self._ndv_of(node.right, col)
        return 100.0


# --------------------------------------------------------------------------
# Client environment (simulated clock + caches)
# --------------------------------------------------------------------------

# Process-wide counters of the client's prefetch-cache index
# (``index_builds``, ``index_reuses``): one build per prefetched key column,
# a reuse for every later ``cacheByColumn`` of it.
# ``ServingRuntime.metrics_snapshot()`` surfaces them as ``client_*``.
CLIENT = MetricsRegistry()

# a serving process sees unbounded distinct prefetched results; the client
# pins at most this many indexes, each with its host image
_INDEX_CAP = 16
# a prefetch's rows reach the host a page of its index order at a time, at
# the first lookup that reads the page: a prefetch looked up at a few keys
# pulls a few pages, and a page read again is pulled once
_PAGE_ROWS = 1 << 14


@jax.jit
def _gather(columns, pos):
    return tuple(c[pos] for c in columns)


class _HostImage:
    """The rows of a prefetched table in its index's order, on the host:
    ``values[j][i]`` is column ``j`` of the row at sorted position ``i``.
    Each page is gathered on the device and pulled at its first read."""

    __slots__ = ("columns", "order", "page", "values", "pulled")

    def __init__(self, columns: tuple, order: np.ndarray):
        self.columns = columns
        self.order = order
        n = len(order)
        self.page = max(1, min(_PAGE_ROWS, n))
        self.values = [np.empty(n, c.dtype) for c in columns]
        self.pulled = np.zeros(-(-n // self.page), bool)

    def slice(self, lo: int, hi: int) -> list:
        """Each column's values at sorted positions ``lo`` to ``hi - 1``."""
        for p in range(lo // self.page, (hi - 1) // self.page + 1):
            if not self.pulled[p]:
                self._pull(p)
        return [v[lo:hi] for v in self.values]

    def _pull(self, p: int) -> None:
        lo = p * self.page
        hi = min(lo + self.page, len(self.order))
        # every page has the one shape: the gather compiles once per table
        pos = np.zeros(self.page, np.int32)
        pos[:hi - lo] = self.order[lo:hi]
        pages = _gather(self.columns,
                        to_device(pos, np.int32, "database.lookup_cache"))
        for v, page in zip(self.values, pages):
            v[lo:hi] = to_host(page, "database.lookup_cache")[:hi - lo]
        self.pulled[p] = True


class _CacheIndex:
    """A prefetched key column in sorted order: ``order`` is its stable
    sort and ``keys`` the keys in that order; :meth:`image` holds the rows
    of the table last looked up through it."""

    __slots__ = ("order", "keys", "_image")

    def __init__(self, key_col):
        arr = to_host(key_col, "database.cache_by_column")
        self.order = np.argsort(arr, kind="stable")
        self.keys = arr[self.order]
        self._image: Optional[_HostImage] = None

    def image(self, columns: tuple) -> _HostImage:
        """The host image of the table whose columns are ``columns``: kept
        while the table keeps its arrays, made anew for one that shares
        only the key column."""
        img = self._image
        if img is None or len(img.columns) != len(columns) or any(
                a is not b for a, b in zip(img.columns, columns)):
            img = self._image = _HostImage(columns, self.order)
        return img


# Keyed by the key column's array, which a re-wrapped ``Table(name, schema,
# t.columns)`` shares with ``t``: the site cache hands back the same result
# for an unchanged site, so its index is sorted and each page pulled once.
_INDEXES = TableMemo(_CacheIndex, _INDEX_CAP)


class ClientEnv:
    """Application-side runtime: clock, ORM id-cache, prefetch cache.

    Charges time per Sec. VI:
        C_Q = C_NRT + C_Q^F + max(N_Q*S_row/BW, C_Q^L − C_Q^F)
    """

    # the prefetch cache's build and lookups open ``client.cache_by_column``
    # and ``client.lookup`` spans on it; a batching env carries the
    # session's tracer
    tracer = NOOP_TRACER

    def __init__(self, db: DatabaseServer, network: NetworkProfile,
                 c_z: float = 30e-9, orm_cache: bool = True):
        self.db = db
        self.network = network
        self.c_z = c_z              # per-imperative-statement cost (paper: 30ns)
        self.clock = 0.0
        self.orm_cache_enabled = orm_cache
        self._orm_cache: Dict[Tuple[str, object], Dict[str, object]] = {}
        self._prefetch_cache: Dict[Tuple[str, str], Dict[object, list]] = {}
        self.query_log: list = []
        self.n_queries = 0
        self.n_round_trips = 0
        # (site_key, iteration_count) per executed while loop / collection-
        # source cursor loop — the observations the feedback controller
        # folds into an ExecutionContext's StatsProfile
        self.iteration_log: list = []

    def record_iterations(self, site: str, count: int) -> None:
        self.iteration_log.append((site, int(count)))

    # ---------------------------------------------------------------- clock
    def charge_statement(self, n: int = 1) -> None:
        self.clock += self.c_z * n

    def _charge_query(self, n_rows: int, row_bytes: int, first_s: float, last_s: float) -> float:
        transfer = n_rows * row_bytes / self.network.bandwidth_bytes_per_s
        cost = self.network.c_nrt + first_s + max(transfer, last_s - first_s)
        self.clock += cost
        self.n_queries += 1
        self.n_round_trips += 1
        return cost

    # --------------------------------------------------------------- queries
    def execute_query(self, q: Query, params: Optional[Mapping[str, object]] = None) -> Table:
        result, first_s, last_s = self.db.run(q, params)
        cost = self._charge_query(result.nrows, result.row_bytes, first_s, last_s)
        self.query_log.append((q.sql(), result.nrows, cost))
        return result

    def point_lookup(self, table: str, key_col: str, key_val) -> Optional[Dict[str, object]]:
        """ORM-style navigation (o.customer): point query w/ Hibernate id-cache."""
        ck = (table, key_val)
        if self.orm_cache_enabled and ck in self._orm_cache:
            self.charge_statement()
            return self._orm_cache[ck]
        t = self.db.table(table)
        # index lookup: server time is one B-tree probe, one row out
        arr = to_host(t.column(key_col), "database.point_lookup")
        idx = np.flatnonzero(arr == key_val)
        m = self.db.model
        self._charge_query(len(idx), t.row_bytes,
                           m.startup_s + m.index_lookup_s,
                           m.startup_s + m.index_lookup_s + len(idx) / m.emit_rows_per_s)
        self.query_log.append((f"SELECT * FROM {table} WHERE {key_col} = {key_val}", len(idx), None))
        if len(idx) == 0:
            return None
        row = t.row(int(idx[0]))
        if self.orm_cache_enabled:
            self._orm_cache[ck] = row
        return row

    # --------------------------------------------------- prefetch cache (N1)
    def cache_by_column(self, t: Table, col: str) -> None:
        """``Utils.cacheByColumn`` from the paper (footnote 3)."""
        # building the local hash index costs C_Z per row
        self.charge_statement(t.nrows)
        key_col = t.column(col)
        with self.tracer.span("client.cache_by_column", table=t.name,
                              rows=t.nrows) as sp:
            idx = _INDEXES.get(key_col)
            built = idx is None
            if built:
                idx = _INDEXES(key_col)
            CLIENT.inc("index_builds" if built else "index_reuses")
            if self.tracer.enabled:
                sp.attrs["built"] = built
        # store as (table, sorted keys, order) for O(log n) lookups, with
        # the index, whose host image serves the rows
        self._prefetch_cache[(t.name, col)] = {
            "table": t, "keys": idx.keys, "order": idx.order, "index": idx,
        }

    def lookup_cache(self, table_name: str, col: str, key_val) -> Optional[Dict[str, object]]:
        rows = self._lookup(table_name, col, key_val, first=True)
        return rows[0] if rows else None

    def lookup_cache_all(self, table_name: str, col: str, key_val) -> list:
        return self._lookup(table_name, col, key_val, first=False)

    def _lookup(self, table_name: str, col: str, key_val, first: bool) -> list:
        """The rows of a prefetch cache whose key is ``key_val`` (the first
        alone where ``first``), in table order: the values ``Table.row``
        gives, read from the index's host image of the table."""
        entry = self._prefetch_cache.get((table_name, col))
        if entry is None:
            raise KeyError(f"no prefetch cache for ({table_name}, {col})")
        self.charge_statement()
        tracer = self.tracer
        with tracer.span("client.lookup") as sp:
            keys = entry["keys"]
            k = search_key(keys, key_val)
            rows = []
            if k is not None:
                lo = keys.searchsorted(k, side="left")
                hi = keys.searchsorted(k, side="right")
                if first:
                    hi = min(hi, lo + 1)
                if hi > lo:
                    t = entry["table"]
                    names = t.schema.names
                    image = entry["index"].image(
                        tuple(t.columns[n] for n in names))
                    cols = [v.tolist() for v in image.slice(lo, hi)]
                    rows = [dict(zip(names, vals)) for vals in zip(*cols)]
            if tracer.enabled:
                sp.attrs["n_rows"] = len(rows)
        return rows

    def has_cache(self, table_name: str, col: str) -> bool:
        return (table_name, col) in self._prefetch_cache

    def reset(self) -> None:
        self.clock = 0.0
        self._orm_cache.clear()
        self._prefetch_cache.clear()
        self.query_log.clear()
        self.n_queries = 0
        self.n_round_trips = 0
