"""A bounded memo of values derived from immutable tables and columns.

Tables and their column arrays never change: a write or ``analyze()``
makes a new :class:`~repro.relational.table.Table`. So anything derived
from one (a sort, host copies of its columns) stays valid for as long as
the object lives, and can be keyed by its identity.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable

__all__ = ["TableMemo"]


class TableMemo:
    """LRU memo of ``build(table, *args)``, keyed by the table's identity
    (and ``args``) WITH a strong reference to the keyed table (``id``
    alone could be recycled). In the serving path the site cache returns
    the same Table object for an unchanged site, so repeated batches hit
    this memo instead of re-deriving from the table. The keyed object may
    be a column array too: a re-wrapped ``Table(name, schema, t.columns)``
    holds the same arrays."""

    def __init__(self, build: Callable, cap: int):
        self.build = build
        self.cap = cap
        self._memo: "OrderedDict[tuple, tuple]" = OrderedDict()

    def get(self, t, *args):
        """The memoized value for ``t`` and ``args``, or None: builds
        nothing."""
        k = (id(t),) + args
        hit = self._memo.get(k)
        if hit is not None and hit[0] is t:
            self._memo.move_to_end(k)
            return hit[1]
        return None

    def __call__(self, t, *args):
        value = self.get(t, *args)
        if value is not None:
            return value
        value = self.build(t, *args)
        self._memo[(id(t),) + args] = (t, value)
        while len(self._memo) > self.cap:
            self._memo.popitem(last=False)
        return value
