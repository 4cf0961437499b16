"""The traffic generator's two loops.

A closed-loop mix sends the very requests it sent before the open loop
existed (the digests were taken from that code).  An open-loop mix sends
the closed loop's sizes in the same order, on a schedule that every seed
repeats and that holds the mix's mean rate.
"""
import hashlib
import threading
import time

import numpy as np
import pytest

import smoke  # noqa: F401
import harness
import loadgen
import spec

SEED = 2**33 + 5


def _mix(name):
    return spec.load_json(spec.HERE / "traffic" / f"{name}.json")


@pytest.mark.parametrize("mix,clients,max_len,want", [
    ("chat", 16, 1536, "eece9a4281d7f352"),
    ("longdoc", 6, 2048, "f6d82e2a6ca29a6c"),
])
def test_closed_loop_sends_what_it_sent_before(mix, clients, max_len, want):
    assert _mix(mix)["loop"] == "closed"
    loop = loadgen.ClosedLoop(_mix(mix), 32256, max_len, clients, SEED)
    d = hashlib.sha256()
    for _ in range(5):
        for c in range(clients):
            p, o = loop.next(c)
            d.update(p.tobytes())
            d.update(int(o).to_bytes(4, "little"))
    assert d.hexdigest()[:16] == want


def test_open_loop_sends_the_closed_loops_sizes_in_order():
    """Each block of the open loop's requests holds the sizes of the closed
    loop's round of the same number, in the mix's order."""
    mix = _mix("chat-poisson")
    assert mix["loop"] == "open"
    opened = loadgen.OpenLoop(mix, 32256, 1536, 16, SEED)
    closed = loadgen.ClosedLoop(mix, 32256, 1536, 16, SEED)
    for k in range(3):
        block = [opened.request(16 * k + c) for c in range(16)]
        assert [(len(p), o) for p, o in block] == closed.sizes[16 * k:
                                                               16 * k + 16]
        round_ = [closed.next(c) for c in range(16)]
        assert (sorted((len(p), o) for p, o in block)
                == sorted((len(p), o) for p, o in round_))


def test_arrivals_repeat_for_every_seed():
    """Every seed sends the same sizes at the same times; the seed draws
    the token ids."""
    mix = _mix("chat-poisson")
    a, b, other = (loadgen.OpenLoop(mix, 32256, 1536, 16, s)
                   for s in (SEED, SEED, SEED + 1))
    gaps = [a.gap(j) for j in range(200)]
    assert gaps == [b.gap(j) for j in range(200)]
    assert gaps == [other.gap(j) for j in range(200)]
    for j in range(40):
        (p, o), (q, n) = a.request(j), other.request(j)
        assert o == n and len(p) == len(q) and not np.array_equal(p, q)
        assert np.array_equal(p, b.request(j)[0])


def test_open_loop_holds_its_mean_rate():
    mix = _mix("chat-poisson")
    loop = loadgen.OpenLoop(mix, 32256, 1536, 16, SEED)
    total = sum(loop.gap(j) for j in range(10_000))
    assert abs(10_000 / total / mix["rate_per_s"] - 1) < 0.02


class _Engine:
    """Takes requests and never answers them."""

    def __init__(self):
        self.got = []

    def submit(self, prompt, max_new_tokens):
        req = type("Req", (), {})()
        req.rid, req.submit_t = len(self.got), time.perf_counter()
        req.done = threading.Event()
        self.got.append(req)
        return req


def test_arrivals_send_on_schedule_without_answers():
    mix = dict(_mix("chat-poisson"), rate_per_s=200.0)
    loop = loadgen.OpenLoop(mix, 512, 1536, 16, SEED)
    engine = _Engine()
    sender = harness.Arrivals(engine, loop)
    sender.start()
    time.sleep(0.3)
    sender.stop()
    sent = engine.got
    # an open loop does not wait for answers: about 60 sent in 0.3 s
    assert 30 <= len(sent) <= 90
    due = [sender.due[r.rid] for r in sent]
    gaps = np.diff(due)
    assert np.allclose(gaps, [loop.gap(j) for j in range(len(gaps))])
    assert all(r.submit_t >= sender.due[r.rid] for r in sent)
    assert not sender.finished
