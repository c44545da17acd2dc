"""The one traffic generator, and the loops that drive the served path.

A traffic mix is a JSON file of parameters (``bench/traffic/<name>.json``):

``loop``
    ``"closed"``: ``clients`` callers, each issuing its next request as
    soon as its last reply returns; latency runs from the issue time. The
    requests are drawn in blocks of :data:`CLOSED_BLOCK`, in order.
    ``"open"``: ``round(rate_rps * seconds)`` arrivals over the window,
    independent of the replies; latency runs from each request's due time.
``arrivals`` (open loop)
    ``"poisson"``: exponential gaps. Every seed gets the same set of gaps
    (the exponential's quantiles at ``(i + 0.5) / N``), in its own order,
    so seeds change the order of the work and not its amount.
``program`` and ``params``
    The program each request calls, and its parameters: a literal, or
    ``{"key_list": {"length": [lo, hi], "keys": {...}}}``, a list of keys
    whose lengths are the integers ``lo..hi`` in equal shares (each block
    of ``hi - lo + 1`` requests holds every length once, in seeded order),
    and whose keys are ``{"zipfian": theta, "scrambled": true,
    "over": <size>}`` (YCSB's zipfian over ``sizes[over]`` items, ranks
    scrambled by FNV-1a) or ``{"uniform": true, "over": <size>}``.
``warmup_requests``
    Requests served before the window, drawn from their own stream.
``runtime``
    Overrides of the configuration's runtime settings (``batch_size``...).
``check_sample``
    How many served responses of each batch slot the check compares (null:
    every response). A sample per slot compares every position of a batch
    in every run.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import numpy as np

# seed streams: each use of the seed draws from its own
STREAM_DATA, STREAM_WINDOW, STREAM_WARMUP, STREAM_SAMPLE = 0, 1, 2, 3

# requests drawn at a time for a closed loop, whose count is not known
CLOSED_BLOCK = 1024

FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of ``seed`` (any whole number)."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


# ----------------------------------------------------------------- keys

def zipfian_ranks(rng: np.random.Generator, n_items: int, theta: float,
                  count: int) -> np.ndarray:
    """``count`` ranks in ``[0, n_items)`` from YCSB's ZipfianGenerator
    (Gray et al., "Quickly generating billion-record synthetic
    databases"): rank 0 is the most popular, P(rank i) ~ 1/(i+1)^theta."""
    i = np.arange(1, n_items + 1, dtype=np.float64)
    zetan = float(np.sum(i ** -theta))
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n_items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(count)
    uz = u * zetan
    tail = np.floor(n_items * (eta * u - eta + 1.0) ** alpha)
    ranks = np.where(uz < 1.0, 0, np.where(uz < 1.0 + 0.5 ** theta, 1, tail))
    return np.minimum(ranks, n_items - 1).astype(np.int64)


def fnv1a_64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``fnvhash64`` of each value's 8 little-endian bytes, as the
    non-negative signed 64-bit number YCSB takes."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_64, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= FNV_PRIME_64
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def draw_keys(rng: np.random.Generator, spec: dict, sizes: dict,
              count: int) -> np.ndarray:
    n = int(sizes[spec["over"]])
    if "zipfian" in spec:
        ranks = zipfian_ranks(rng, n, float(spec["zipfian"]), count)
        return fnv1a_64(ranks) % n if spec.get("scrambled") else ranks
    if spec.get("uniform"):
        return rng.integers(0, n, count, dtype=np.int64)
    raise ValueError(f"unknown key distribution {spec!r}")


def equal_shares(rng: np.random.Generator, lo: int, hi: int,
                 count: int) -> np.ndarray:
    """``count`` integers of ``lo..hi`` in seeded order, each block of
    ``hi - lo + 1`` holding every value once: any stretch of requests does
    nearly the same work, whatever the seed."""
    vals = np.arange(lo, hi + 1)
    blocks = -(-count // len(vals))
    return np.concatenate([rng.permutation(vals)
                           for _ in range(blocks)])[:count]


# --------------------------------------------------------------- requests

def make_params(traffic: dict, sizes: dict, rng: np.random.Generator,
                count: int) -> List[dict]:
    """The parameters of ``count`` requests."""
    out = [dict() for _ in range(count)]
    for name, spec in traffic.get("params", {}).items():
        if isinstance(spec, dict) and "key_list" in spec:
            kl = spec["key_list"]
            lo, hi = kl["length"]
            lengths = equal_shares(rng, int(lo), int(hi), count)
            keys = draw_keys(rng, kl["keys"], sizes, int(lengths.sum()))
            ends = np.cumsum(lengths)
            for p, e, n in zip(out, ends, lengths):
                p[name] = [int(k) for k in keys[e - n:e]]
        else:
            for p in out:
                p[name] = spec
    return out


def arrival_offsets(traffic: dict, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times of the open loop's requests, in seconds from the window's
    start, all inside ``[0, seconds)``."""
    n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
    kind = traffic.get("arrivals", "poisson")
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / gaps.sum())


# ------------------------------------------------------------- the window

@dataclasses.dataclass
class Window:
    """What one measured window recorded (host clock, ``perf_counter``)."""

    t0: float = 0.0
    t_end: float = 0.0
    due: List[float] = dataclasses.field(default_factory=list)
    done: List[Optional[float]] = dataclasses.field(default_factory=list)
    failed: int = 0
    errors: List[str] = dataclasses.field(default_factory=list)
    lag_s: List[float] = dataclasses.field(default_factory=list)
    batches: int = 0
    round_trips: int = 0

    @property
    def attempted(self) -> int:
        return len(self.due)

    @property
    def completed(self) -> int:
        return sum(d is not None for d in self.done)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def latencies_s(self) -> List[float]:
        """One per request; a failed request counts as missing every
        limit (infinite)."""
        return [(d - u) if d is not None else float("inf")
                for u, d in zip(self.due, self.done)]


class Sampler:
    """Keeps, for the check, a seeded uniform sample of ``k`` served
    responses from each batch slot (reservoir sampling per slot), or every
    response when ``k`` is None."""

    def __init__(self, k: Optional[int], rng: np.random.Generator):
        self.k = k
        self.rng = rng
        self.seen = 0
        self._seen_by_slot: Dict[int, int] = {}
        self._kept_by_slot: Dict[int, list] = {}

    @property
    def kept(self) -> list:
        return [item for slot in sorted(self._kept_by_slot)
                for item in self._kept_by_slot[slot]]

    def offer(self, index: int, params: dict, response, slot: int = 0
              ) -> None:
        item = (index, params, response)
        self.seen += 1
        if self.k is None:
            slot = 0
        seen = self._seen_by_slot[slot] = self._seen_by_slot.get(slot, 0) + 1
        kept = self._kept_by_slot.setdefault(slot, [])
        if self.k is None or len(kept) < self.k:
            kept.append(item)
            return
        j = int(self.rng.integers(0, seen))
        if j < self.k:
            kept[j] = item


def _serve(serve: Callable, program: str, batch: List[int], params, win,
           sampler, clock) -> None:
    try:
        results = serve([(program, params[i]) for i in batch])
        if len(results) != len(batch):
            raise RuntimeError(f"{len(results)} responses to {len(batch)} "
                               f"requests")
    except Exception as e:  # a failed batch fails its requests, the run goes on
        win.failed += len(batch)
        win.errors.append(f"{type(e).__name__}: {e}"[:300])
        win.batches += 1
        return
    now = clock()
    win.batches += 1
    for slot, (i, r) in enumerate(zip(batch, results)):
        if r is None:
            win.failed += 1
            continue
        win.done[i] = now
        win.round_trips += int(getattr(r, "n_round_trips", 0))
        sampler.offer(i, params[i], r.outputs, slot)


def drive_closed(serve: Callable, traffic: dict, params_for: Callable,
                 batch_size: int, seconds: float, sampler: Sampler,
                 annotate=None, clock=time.perf_counter) -> Window:
    """``clients`` callers in a closed loop. Batches start while the window
    is open; the window ends when the last of them returns, so every
    request issued in it is counted with all its time."""
    annotate = annotate or _no_annotation
    program = traffic["program"]
    n_clients = int(traffic["clients"])
    win = Window()
    params: List[dict] = []
    ready: List[int] = []      # request indices issued and not yet served

    def issue(at: float) -> None:
        i = len(params)
        params.append(params_for(i))
        win.due.append(at)
        win.done.append(None)
        ready.append(i)

    win.t0 = clock()
    for _ in range(n_clients):
        issue(win.t0)
    while clock() - win.t0 < seconds:
        batch, ready[:] = ready[:batch_size], ready[batch_size:]
        with annotate("serve"):
            _serve(serve, program, batch, params, win, sampler, clock)
        now = clock()
        for _ in batch:   # each caller whose reply came issues again
            issue(now)
    # requests issued after the last batch started were never sent
    unsent = set(ready)
    win.due = [u for i, u in enumerate(win.due) if i not in unsent]
    win.done = [d for i, d in enumerate(win.done) if i not in unsent]
    win.t_end = max([d for d in win.done if d is not None] or [clock()])
    return win


def drive_open(serve: Callable, traffic: dict, params: List[dict],
               offsets: np.ndarray, seconds: float, batch_size: int,
               sampler: Sampler, annotate=None, clock=time.perf_counter,
               sleep=time.sleep) -> Window:
    """Open loop: each request is due at ``t0 + offsets[i]``, inside the
    ``seconds`` of arrivals; the server takes up to ``batch_size`` due
    requests at a time, first come first served. Arrivals stop at the end
    of those seconds; the queue then drains, and the window ends with the
    later of the last reply and the end of the arrivals."""
    annotate = annotate or _no_annotation
    program = traffic["program"]
    n = len(offsets)
    win = Window()
    win.done = [None] * n
    win.t0 = clock()
    win.due = [win.t0 + float(o) for o in offsets]
    nxt = 0                   # next request not yet due
    queue: List[int] = []
    while nxt < n or queue:
        now = clock()
        while nxt < n and win.due[nxt] <= now:
            queue.append(nxt)
            nxt += 1
        if not queue:
            with annotate("loadgen.wait"):
                wait = win.due[nxt] - clock()
                if wait > 0:
                    sleep(wait)
            win.lag_s.append(clock() - win.due[nxt])
            continue
        batch, queue[:] = queue[:batch_size], queue[batch_size:]
        with annotate("serve"):
            _serve(serve, program, batch, params, win, sampler, clock)
    # the window spans every arrival's period and every reply
    win.t_end = max([d for d in win.done if d is not None]
                    + [win.t0 + float(seconds)])
    return win


def _no_annotation(name: str):
    return contextlib.nullcontext()
