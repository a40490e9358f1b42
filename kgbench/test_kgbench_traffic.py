"""The traffic generator and the two client loops, on a fake clock."""
from collections import Counter

import numpy as np
import pytest

from kgbench.traffic import Mix, Request, closed_loop, open_loop

DOMAINS = {"T:A": [f"a{i}" for i in range(50)], "T:B": ["b0", "b1", "b2"]}
OPEN = {"loop": "open", "rate_qps": 40,
        "mix": {"Q1": 1, "Q2": 1, "Q3": 2},
        "params": {"Q1": [{"constant": "a", "domain_type": "T:A",
                           "dist": "zipf", "zipf_s": 0.99}],
                   "Q3": [{"constant": "a", "domain_type": "T:A",
                           "dist": "uniform"},
                          {"constant": "b", "domain_type": "T:B",
                           "dist": "uniform"}]}}
CLOSED = {"loop": "closed", "clients": 5, "sequence": ["Q1", "Q2", "Q3"],
          "params": OPEN["params"]}


def _key(reqs):
    return [(r.template, r.values, r.due) for r in reqs]


def test_open_schedule_is_seeded_and_exact():
    a = Mix(OPEN, DOMAINS, 2**33 + 5).open_schedule(10.0)
    b = Mix(OPEN, DOMAINS, 2**33 + 5).open_schedule(10.0)
    c = Mix(OPEN, DOMAINS, 2**33 + 6).open_schedule(10.0)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    # whole rounds: the count nearest rate x seconds
    assert len(Mix(OPEN, DOMAINS, 1).open_schedule(1.0)) == 40
    assert len(Mix(OPEN, DOMAINS, 1).open_schedule(0.96)) == 40
    assert len(Mix(OPEN, DOMAINS, 1).open_schedule(0.01)) == 4


def test_every_seed_offers_the_same_work_at_the_same_times():
    """Whole rounds of the mix (Q1, Q2 and twice Q3, in file order),
    evenly spaced, repeated from a seeded position in the round."""
    round_ = ["Q1", "Q2", "Q3", "Q3"]
    starts = set()
    for seed in range(2**33, 2**33 + 12):
        s = Mix(OPEN, DOMAINS, seed).open_schedule(10.0)
        assert len(s) == 400
        assert np.allclose([r.due for r in s], np.arange(400) / 40)
        names = [r.template for r in s]
        start = next(k for k in range(4)
                     if names[:4] == round_[k:] + round_[:k])
        starts.add(start)
        assert names == [round_[(start + i) % 4] for i in range(400)]
    assert len(starts) > 1


def test_draws_stay_in_their_domains():
    s = Mix(OPEN, DOMAINS, 7).open_schedule(50.0)
    for r in s:
        if r.template == "Q1":
            assert len(r.values) == 1 and r.values[0] in DOMAINS["T:A"]
        elif r.template == "Q3":
            assert r.values[0] in DOMAINS["T:A"]
            assert r.values[1] in DOMAINS["T:B"]
        else:
            assert r.values == ()


def test_zipf_is_skewed_and_the_seed_picks_the_hot_value():
    hot = []
    for seed in (1, 2, 3, 4):
        q1 = Counter(r.values[0] for r in
                     Mix(OPEN, DOMAINS, seed).open_schedule(250.0)
                     if r.template == "Q1")
        (top, n), = q1.most_common(1)
        # Zipf(0.99) over 50 values gives the first rank about 22%
        assert 0.15 < n / sum(q1.values()) < 0.30
        hot.append(top)
    assert len(set(hot)) > 1


class FakeClock:
    """A clock that moves only when the loop sleeps or a call stalls."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += max(dt, 1e-6)


class FakeTicket:
    def __init__(self, t):
        self.t_enqueue = t
        self.done = False
        self.t_done = None


def test_open_loop_times_from_the_due_time_and_counts_a_stall():
    clock = FakeClock()
    sched = [Request("Q2", (), d) for d in np.arange(0.0, 2.0, 0.1)]
    sent = []

    def submit(r):
        sent.append(clock())
        return FakeTicket(clock())

    calls = []

    def pump():
        calls.append(clock())
        if len(calls) == 3:          # one pump stalls for 0.55 s
            clock.now += 0.55
        return 0

    open_loop(submit, pump, sched, clock(), 2.0, clock=clock,
              sleep=clock.sleep)
    assert clock() >= 102.0
    lags = [r.submit - r.due for r in sched]
    # requests that fell due in the stall went out late, and their lag
    # (hence their latency from the due time) holds the stall
    assert max(lags) > 0.4
    assert sum(1 for x in lags if x > 0.05) >= 5
    assert all(r.due == pytest.approx(100.0 + 0.1 * i)
               for i, r in enumerate(sched))


def test_closed_loop_keeps_exactly_n_outstanding():
    clock = FakeClock()
    live = []
    seen = []

    def submit(r):
        t = FakeTicket(clock())
        live.append(t)
        return t

    def pump():
        # finish the oldest request every pump; record what is in flight
        seen.append(sum(1 for t in live if not t.done))
        for t in live:
            if not t.done:
                t.done, t.t_done = True, clock()
                clock.now += 0.01
                return 1
        return 0

    mix = Mix(CLOSED, DOMAINS, 9)
    sent = closed_loop(submit, pump, mix, clock(), 1.0, clock=clock,
                       sleep=clock.sleep)
    assert set(seen) == {5}
    assert len(sent) > 50
    # each client runs the sequence in order from its own position
    assert [r.template for r in sent[:5]] == ["Q1", "Q2", "Q3", "Q1", "Q2"]
    again = closed_loop(lambda r: FakeTicket(0), lambda: 0, Mix(CLOSED,
                        DOMAINS, 9), 0.0, 0.0, clock=lambda: 1.0,
                        sleep=lambda dt: None)
    assert [(r.template, r.values) for r in again] == \
        [(r.template, r.values) for r in sent[:5]]
