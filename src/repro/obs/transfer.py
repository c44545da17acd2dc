"""Counted host↔device transfers on the data path.

Every site of the relational substrate, the columnar executor and the
compiled tier that pulls a ``jax.Array`` to the host or puts host data on
the device goes through :func:`to_host` or :func:`to_device`. Each makes
exactly the call it stands for — ``np.asarray(x)`` (JAX's ``x.item()`` is
``np.asarray(x).item()``) or ``jnp.asarray(x, dtype)`` — with no sync and
no batching of its own, and counts into the one process-wide
:data:`TRANSFERS` registry, labelled by ``site``:

  * ``host_reads``: one per blocking pull of a device array;
  * ``d2h_bytes``: the bytes of those pulls;
  * ``h2d_bytes``: the bytes of host data put on the device.

A host numpy array counts nothing, and neither does a device array whose
host copy JAX already holds (it keeps one after the first pull that had to
copy): neither moves a byte. On the CPU backend a pull is a zero-copy view
that JAX does not keep, so there every pull of a device array counts.

``ServingRuntime.metrics_snapshot()`` surfaces the counters under
``transfer_``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np
# the concrete jax.Array: isinstance against the abstract class costs
# ~0.4 us, a share of a small pull worth keeping off the count
from jax._src.array import ArrayImpl

from .metrics import MetricsRegistry, _key

__all__ = ["TRANSFERS", "to_host", "to_device"]


class _TransferCounters(MetricsRegistry):
    """A :class:`MetricsRegistry` with a fast path for the helpers: each
    site's counter keys are resolved once, so a count is two dict
    updates."""

    def __init__(self):
        super().__init__()
        self._site_keys: Dict[str, Tuple[tuple, tuple, tuple]] = {}

    def _keys(self, site: str) -> Tuple[tuple, tuple, tuple]:
        keys = self._site_keys.get(site)
        if keys is None:
            keys = self._site_keys[site] = tuple(
                _key(name, {"site": site})
                for name in ("host_reads", "d2h_bytes", "h2d_bytes"))
        return keys

    def count_read(self, site: str, nbytes: int) -> None:
        reads, d2h, _ = self._keys(site)
        c = self._counters
        c[reads] = c.get(reads, 0) + 1
        c[d2h] = c.get(d2h, 0) + nbytes

    def count_write(self, site: str, nbytes: int) -> None:
        h2d = self._keys(site)[2]
        self._counters[h2d] = self._counters.get(h2d, 0) + nbytes


TRANSFERS = _TransferCounters()


def to_host(x, site: str) -> np.ndarray:
    """``np.asarray(x)``, counted as a host read of its bytes where ``x``
    is a device array not yet copied to the host."""
    if isinstance(x, ArrayImpl) and x._npy_value is None:
        out = np.asarray(x)
        TRANSFERS.count_read(site, out.nbytes)
        return out
    return np.asarray(x)


def to_device(x, dtype, site: str):
    """``jnp.asarray(x, dtype)``, counted as host-to-device bytes where
    ``x`` is host data (a device array converts on the device)."""
    out = jnp.asarray(x, dtype=dtype)
    if not isinstance(x, ArrayImpl):
        TRANSFERS.count_write(site, out.nbytes)
    return out
