"""The one traffic generator, and the open- and closed-loop clients.

A traffic mix is a JSON file under ``kgbench/traffic/``; this module reads
every mix, so a new mix is a new data file and no new code. Keys:

- ``loop``: ``"open"`` (independent users: arrivals on a schedule, whatever
  the server does) or ``"closed"`` (each client waits for its answer).
- open loop: ``rate_qps`` and ``mix`` ({template: requests per round}).
  One round is the mix in file order, each template as many times as its
  count. The window holds the whole number of rounds nearest to
  ``rate_qps * seconds`` arrivals, evenly spaced over the window, and the
  rounds repeat from a position in the round that the seed picks: every
  seed offers the same work at the same arrival times, in another order.
  Such a mix judges service time, not queueing: no bursts. (Poisson gaps,
  even in a seeded order of fixed quantiles, let the 95th percentile of a
  51 s window swing by more than its median from seed to seed: the order
  of the bursts, not the program, set it.)
- closed loop: ``clients`` and ``sequence`` (the templates of one query mix,
  run in order). Client c starts at position c of the sequence.
- ``params``: {template: [param, ...]}. A param names the ``constant`` of the
  template that it replaces (every occurrence), its domain ``domain_type``
  (the subjects of ``rdf:type <domain_type>``), and ``dist``: ``uniform``,
  or ``zipf`` with exponent ``zipf_s`` over the domain in a rank order that
  the seed permutes.

Latency is timed from each request's due time (open loop) or from its
submission (closed loop).
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    """One request as the client sees it."""
    template: str
    values: tuple            # parameter values (term strings), in order
    due: float               # when the client meant to send it
    submit: float = 0.0      # when it was handed to the server
    ticket: object = None    # the server's Ticket


class ParamDraw:
    """Seeded draws of one parameter's values over its domain."""

    def __init__(self, spec: dict, domain: list[str], rng):
        self.domain = list(domain)
        if not self.domain:
            raise ValueError(f"empty domain for {spec['constant']}")
        n = len(self.domain)
        if spec["dist"] == "uniform":
            self.p = None
        elif spec["dist"] == "zipf":
            w = 1.0 / np.arange(1, n + 1) ** float(spec["zipf_s"])
            # the seed decides which value is hottest
            self.p = np.empty(n)
            self.p[rng.permutation(n)] = w / w.sum()
        else:
            raise ValueError(f"unknown dist {spec['dist']!r}")

    def draw(self, rng) -> str:
        """One value."""
        i = rng.integers(len(self.domain)) if self.p is None \
            else rng.choice(len(self.domain), p=self.p)
        return self.domain[int(i)]


class Mix:
    """A traffic file bound to its domains and a seed."""

    def __init__(self, traffic: dict, domains: dict[str, list[str]],
                 seed: int):
        self.traffic = traffic
        self.seed = int(seed)
        ss = np.random.SeedSequence(self.seed)
        self._param_ss, self._order_ss, self._client_ss = ss.spawn(3)
        rng = np.random.default_rng(self._param_ss)
        self.draws = {
            name: [ParamDraw(p, domains[p["domain_type"]], rng)
                   for p in specs]
            for name, specs in sorted(traffic.get("params", {}).items())}

    def values(self, template: str, rng) -> tuple:
        """Parameter values of one request of `template`."""
        return tuple(d.draw(rng) for d in self.draws.get(template, ()))

    def open_schedule(self, seconds: float) -> list[Request]:
        """The open loop's requests, due at offsets in [0, seconds)."""
        t = self.traffic
        one = [name for name in t["mix"] for _ in range(int(t["mix"][name]))]
        rounds = max(1, round(float(t["rate_qps"]) * seconds / len(one)))
        n = rounds * len(one)
        rng = np.random.default_rng(self._order_ss)
        start = int(rng.integers(len(one)))
        names = [one[(start + i) % len(one)] for i in range(n)]
        return [Request(name, self.values(name, rng), i * seconds / n)
                for i, name in enumerate(names)]

    def client_rng(self, client: int):
        """The closed loop's generator for one client."""
        return np.random.default_rng(
            np.random.SeedSequence(self._client_ss.entropy,
                                   spawn_key=(7, client)))


def _span(annotate, name):
    return annotate(name) if annotate is not None else nullcontext()


def open_loop(submit, pump, schedule: list[Request], t0: float,
              seconds: float, *, clock, sleep=time.sleep, annotate=None,
              idle_s: float = 5e-4) -> list[Request]:
    """Send each request at t0 + its due offset, pumping in between, until
    t0 + seconds.

    `submit(request)` returns the server's Ticket; `pump()` advances the
    server. Due offsets become absolute due times on `clock`. A request is
    sent as soon as it is due; when the loop runs late (a long pump), every
    request that fell due meanwhile goes out at once, still timed from its
    due time. Returns the requests sent, all of them.
    """
    for r in schedule:
        r.due += t0
    end = t0 + seconds
    i = 0
    while True:
        now = clock()
        if now >= end and i == len(schedule):
            return schedule
        while i < len(schedule) and schedule[i].due <= now:
            r = schedule[i]
            r.submit = clock()
            with _span(annotate, "kgbench/submit"):
                r.ticket = submit(r)
            i += 1
        with _span(annotate, "kgbench/pump"):
            busy = pump()
        if not busy:
            nxt = schedule[i].due if i < len(schedule) else end
            sleep(max(0.0, min(idle_s, nxt - clock())))


def closed_loop(submit, pump, mix: Mix, t0: float, seconds: float, *,
                clock, sleep=time.sleep, annotate=None,
                idle_s: float = 5e-4) -> list[Request]:
    """`clients` clients, each sending its next request as soon as its
    last is answered, until t0 + seconds; then no more are sent.

    Each client runs ``sequence`` in order from its own position, with
    parameters from its own seeded generator. Returns every request sent.
    """
    t = mix.traffic
    seq = list(t["sequence"])
    n_clients = int(t["clients"])
    rngs = [mix.client_rng(c) for c in range(n_clients)]
    pos = [c % len(seq) for c in range(n_clients)]
    sent: list[Request] = []
    outstanding: dict[int, Request] = {}
    end = t0 + seconds

    def send(c: int) -> None:
        name = seq[pos[c]]
        pos[c] = (pos[c] + 1) % len(seq)
        now = clock()
        r = Request(name, mix.values(name, rngs[c]), now, now)
        with _span(annotate, "kgbench/submit"):
            r.ticket = submit(r)
        sent.append(r)
        outstanding[c] = r

    for c in range(n_clients):
        send(c)
    while outstanding:
        with _span(annotate, "kgbench/pump"):
            busy = pump()
        finished = [c for c, r in outstanding.items() if r.ticket.done]
        for c in finished:
            del outstanding[c]
            if clock() < end:
                send(c)
        if not busy and not finished:
            sleep(idle_s)
        if clock() >= end:
            break
    return sent
