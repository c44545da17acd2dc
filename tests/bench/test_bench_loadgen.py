"""The traffic generator: determinism per seed, the Zipf and scramble
shape, equal shares of sizes, one set of gaps for every seed, and the
check's sample of every batch slot."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import loadgen  # noqa: E402

ZIPF_TRAFFIC = {
    "loop": "open", "rate_rps": 50.0, "program": "W_E",
    "params": {"worklist": {"key_list": {
        "length": [1, 16],
        "keys": {"zipfian": 0.99, "scrambled": True, "over": "n"}}}},
}
SIZES = {"n": 100_000}


def test_same_seed_same_requests_other_seed_other_order():
    a = loadgen.make_params(ZIPF_TRAFFIC, SIZES, loadgen.rng_for(7, 1), 192)
    b = loadgen.make_params(ZIPF_TRAFFIC, SIZES, loadgen.rng_for(7, 1), 192)
    c = loadgen.make_params(ZIPF_TRAFFIC, SIZES, loadgen.rng_for(8, 1), 192)
    assert a == b
    assert a != c
    # every seed gets the same multiset of worklist lengths
    assert sorted(len(p["worklist"]) for p in a) == \
        sorted(len(p["worklist"]) for p in c)


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, -3])
def test_seed_streams_are_apart_and_large_seeds_work(seed):
    draws = [loadgen.rng_for(seed, s).integers(0, 2**62) for s in range(4)]
    assert len(set(draws)) == 4


def test_equal_shares_of_lengths_in_every_block():
    lengths = loadgen.equal_shares(np.random.default_rng(0), 1, 16, 168)
    assert len(lengths) == 168
    for b in range(10):
        assert sorted(lengths[16 * b:16 * b + 16]) == list(range(1, 17))
    assert len(set(lengths[160:])) == 8


def test_zipfian_shape():
    n, theta, count = 100_000, 0.99, 400_000
    ranks = loadgen.zipfian_ranks(np.random.default_rng(1), n, theta, count)
    assert ranks.min() >= 0 and ranks.max() < n
    h = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -theta)
    # rank 0 carries 1/zeta(n); the top 4096 ranks H(4096)/H(n) of draws
    assert np.mean(ranks == 0) == pytest.approx(1 / h[-1], rel=0.05)
    assert np.mean(ranks < 4096) == pytest.approx(h[4095] / h[-1], abs=0.02)


def test_scramble_spreads_the_hot_ranks():
    n = 100_000
    ranks = np.arange(1000)
    keys = loadgen.fnv1a_64(ranks) % n
    assert keys.min() >= 0 and keys.max() < n
    # the hot ranks land all over the key space, not at its start
    assert np.mean(keys < 1000) < 0.05
    assert len(set(keys.tolist())) > 990
    # YCSB's fnvhash64, written out on Python integers
    for v in (0, 1, 4095, 99_999):
        h, x = 0xCBF29CE484222325, v
        for _ in range(8):
            h ^= x & 0xFF
            h = (h * 1099511628211) % 2**64
            x >>= 8
        signed = h - 2**64 if h >= 2**63 else h
        assert int(loadgen.fnv1a_64(np.array([v]))[0]) == abs(signed)


def test_arrivals_one_set_of_gaps_for_every_seed():
    t = {"rate_rps": 40.0, "arrivals": "poisson"}
    a = loadgen.arrival_offsets(t, 10.0, loadgen.rng_for(1, 1))
    b = loadgen.arrival_offsets(t, 10.0, loadgen.rng_for(2, 1))
    assert len(a) == len(b) == 400
    assert a[0] == 0.0 and a[-1] < 10.0 and np.all(np.diff(a) > 0)
    ga = np.sort(np.concatenate([np.diff(a), [10.0 - a[-1]]]))
    gb = np.sort(np.concatenate([np.diff(b), [10.0 - b[-1]]]))
    assert ga.sum() == pytest.approx(10.0)
    assert np.allclose(ga, gb)
    assert not np.array_equal(a, b)


def test_the_sample_holds_every_batch_slot():
    sampler = loadgen.Sampler(1, np.random.default_rng(0))
    for batch in range(70):
        for slot in range(8):
            sampler.offer(8 * batch + slot, {}, None, slot)
    kept = [i for i, _p, _r in sampler.kept]
    assert sampler.seen == 560 and len(kept) == 8
    assert sorted(i % 8 for i in kept) == list(range(8))
    # each slot's pick is drawn from the whole window, not its first batch
    assert max(kept) >= 8
    every = loadgen.Sampler(None, np.random.default_rng(0))
    for i in range(5):
        every.offer(i, {}, None, i % 2)
    assert [i for i, _p, _r in every.kept] == [0, 1, 2, 3, 4]


def test_open_loop_times_from_due_and_counts_lag():
    clock = [0.0]

    def now():
        return clock[0]

    def sleep(s):
        clock[0] += s + 0.001       # one millisecond late each wake-up

    def serve(reqs):
        clock[0] += 0.01 * len(reqs)
        return [type("R", (), {"outputs": {"x": 1}, "n_round_trips": 2})()
                for _ in reqs]

    sampler = loadgen.Sampler(None, np.random.default_rng(0))
    w = loadgen.drive_open(serve, {"program": "P"}, [{}] * 3,
                           np.array([0.0, 1.0, 1.001]), 2.0, 2, sampler,
                           clock=now, sleep=sleep)
    assert w.completed == 3 and w.failed == 0 and w.round_trips == 6
    lat = w.latencies_s()
    assert lat[0] == pytest.approx(0.01)
    # request 1 waited out a late wake-up; request 2 was served with it
    assert lat[1] == pytest.approx(0.001 + 0.02)
    assert lat[2] == pytest.approx(0.02)
    assert w.lag_s == [pytest.approx(0.001)]
    assert len(sampler.kept) == 3
    # the window lasts the arrivals' 2 s, though the replies came earlier
    assert w.seconds == pytest.approx(2.0)


def test_closed_loop_reissues_on_reply_and_counts_whole_batches():
    clock = [0.0]

    def serve(reqs):
        clock[0] += 0.5
        return [type("R", (), {"outputs": {}, "n_round_trips": 0})()
                for _ in reqs]

    sampler = loadgen.Sampler(2, np.random.default_rng(0))
    w = loadgen.drive_closed(serve, {"program": "P", "clients": 4},
                             lambda i: {}, 4, 1.2, sampler,
                             clock=lambda: clock[0])
    # batches start at 0, 0.5 and 1.0; the last returns at 1.5
    assert w.attempted == w.completed == 12 and w.batches == 3
    assert w.seconds == pytest.approx(1.5)
    assert all(v == pytest.approx(0.5) for v in w.latencies_s())
    # two of each of the four slots
    assert len(sampler.kept) == 8 and sampler.seen == 12


def test_a_failed_batch_counts_its_requests_as_failed():
    def serve(reqs):
        raise RuntimeError("boom")

    w = loadgen.drive_open(serve, {"program": "P"}, [{}] * 2,
                           np.array([0.0, 0.0]), 1.0, 4,
                           loadgen.Sampler(None, np.random.default_rng(0)))
    assert w.failed == 2 and w.completed == 0
    assert all(v == float("inf") for v in w.latencies_s())
