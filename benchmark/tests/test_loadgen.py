"""The open-loop schedule and its accounting against a fake clock and a
fake server; the seeded generators."""

import numpy as np
import pytest

from benchmark.lib import loadgen, synth


class FakeWorld:
    """A clock that only moves when the generator waits, and a server
    that answers each request ``service_s`` after it was sent, one
    request per connection at a time."""

    def __init__(self, slots, service_s, stall=(0.0, 0.0)):
        self.now, self.slots, self.service_s, self.stall = 100.0, slots, service_s, stall
        self.pending = {}  # slot -> time the answer arrives
        self.resets = 0

    def clock(self):
        return self.now

    def send(self, slot, n):
        ready = self.now + self.service_s
        if self.stall[0] <= self.now - 100.0 < self.stall[1]:
            ready = 100.0 + self.stall[1] + self.service_s
        self.pending[slot] = ready

    def poll(self, timeout):
        # a poll that does not wait still takes its ten microseconds
        step = max(timeout, 1e-5)
        first = min(self.pending.values(), default=float("inf"))
        self.now = max(self.now, min(self.now + step, first))
        done = [s for s, t in self.pending.items() if t <= self.now]
        for s in done:
            del self.pending[s]
        return [(s, 200, b"{}") for s in done]

    def reset(self, slot):
        self.resets += 1
        self.pending.pop(slot, None)


def test_open_loop_sends_on_schedule_and_times_from_due():
    world = FakeWorld(slots=4, service_s=0.010)
    offsets = np.arange(50) * 0.02
    got = loadgen.drive("open", 50, offsets, 1.0, 5.0, world, world.clock, 100.0)
    assert len(got["status"]) == 50 and (got["status"] == 200).all()
    np.testing.assert_allclose(got["due"], offsets, atol=1e-9)
    # never early; late by at most the poll's millisecond
    assert (got["sent"] >= got["due"] - 1e-9).all()
    assert (got["sent"] - got["due"]).max() < 2e-3
    np.testing.assert_allclose(got["done"] - got["sent"], 0.010, atol=2e-3)


def test_a_stall_is_charged_to_the_requests_it_delayed():
    # one connection, 10 ms service, arrivals every 5 ms: requests queue
    # in the generator; latency from DUE grows, latency from SENT does not
    world = FakeWorld(slots=1, service_s=0.010)
    offsets = np.arange(20) * 0.005
    got = loadgen.drive("open", 20, offsets, 0.1, 5.0, world, world.clock, 100.0)
    from_due = got["done"] - got["due"]
    from_sent = got["done"] - got["sent"]
    assert from_sent.max() < 0.013
    assert from_due[-1] > 0.09  # the 20th waited for 19 services
    assert (got["sent"] - got["due"])[-1] > 0.08  # and the lateness says so


def test_timeout_marks_the_request_and_resets_the_connection():
    world = FakeWorld(slots=2, service_s=0.010, stall=(0.0, 30.0))
    got = loadgen.drive(
        "open", 2, np.array([0.0, 0.001]), 1.0, 0.5, world, world.clock, 100.0)
    assert (got["status"] == loadgen.TIMED_OUT).all() and world.resets == 2


def test_closed_loop_keeps_every_caller_busy_until_the_window_closes():
    world = FakeWorld(slots=3, service_s=0.010)
    got = loadgen.drive("closed", 10_000, None, 0.5, 5.0, world, world.clock, 100.0)
    n = len(got["status"])
    assert 3 * 40 <= n <= 3 * 51  # 3 callers x ~50 services of 10 ms
    assert (got["sent"] < 0.5).all() and (got["status"] == 200).all()


def test_request_bytes_are_one_http_request():
    raw = loadgen.request_bytes(17, 10)
    head, body = raw.split(b"\r\n\r\n")
    assert head.startswith(b"POST /queries.json HTTP/1.1\r\n")
    assert f"Content-Length: {len(body)}".encode() in head
    assert body == b'{"user": "u17", "num": 10}'


SIZES = {"n_users": 300, "n_items": 90, "n_ratings": 5000, "rank": 8}
LAW = {"user_exponent": 0.8, "item_exponent": 0.9, "truth_rank": 4,
       "noise_sd": 0.5, "rating_mean": 3.5}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_generators_are_deterministic_in_the_seed(seed):
    a, b = synth.ratings(SIZES, LAW, seed), synth.ratings(SIZES, LAW, seed)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(
        synth.zipf_users(300, 0.8, 1000, seed), synth.zipf_users(300, 0.8, 1000, seed))
    np.testing.assert_array_equal(
        synth.poisson_offsets(100.0, 3.0, seed), synth.poisson_offsets(100.0, 3.0, seed))
    for x, y in zip(synth.factor_tables(SIZES, seed), synth.factor_tables(SIZES, seed)):
        np.testing.assert_array_equal(x, y)


def test_seeds_change_the_order_and_not_the_sizes():
    u1, i1, r1, _ = synth.ratings(SIZES, LAW, 1)
    u2, i2, r2, _ = synth.ratings(SIZES, LAW, 2)
    assert not np.array_equal(u1, u2) and not np.array_equal(r1, r2)
    for a, b, n in ((u1, u2, 300), (i1, i2, 90)):
        da, db = np.bincount(a, minlength=n), np.bincount(b, minlength=n)
        np.testing.assert_array_equal(np.sort(da), np.sort(db))
        assert da.min() >= 1 and da.sum() == 5000
    assert len(synth.poisson_offsets(100.0, 3.0, 1)) == len(synth.poisson_offsets(100.0, 3.0, 2)) == 300


def test_holdout_pairs_are_outside_the_training_set():
    u, i, r, truth = synth.ratings(SIZES, LAW, 5)
    hu, hi, hr = synth.holdout(SIZES, LAW, 5, truth, u, i, 400)
    assert len(hu) == 400
    train = set(zip(u.tolist(), i.tolist()))
    assert not train & set(zip(hu.tolist(), hi.tolist()))
    assert abs(float(np.mean(hr)) - 3.5) < 0.2
