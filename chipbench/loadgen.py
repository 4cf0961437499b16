"""The one traffic generator: requests over a fixed set of sizes, sent by a
closed or an open loop.

A mix (``traffic/<mix>.json``) gives the distributions of prompt and output
lengths and how many requests of distinct size make its set.  The set of
sizes is the same for every seed: lengths sit at evenly spaced quantiles of
the distributions, paired and ordered by a permutation drawn from the mix's
own ``size_seed``.  ``--seed`` only deals each round's sizes out among the
clients and draws the token ids, so two seeds ask for the same work in
another order.

The mix's ``loop`` says how requests are sent.  ``"closed"``: every client
sends its next request as soon as its last one is answered.  ``"open"``:
requests are sent at ``rate_per_s`` on average whether or not earlier ones
are answered, after gaps of a Poisson process's exponential distribution.
The gaps too are a set fixed by the mix, at evenly spaced quantiles.  In
an open loop the seed draws only the token ids: every seed sends the same
sizes at the same times, because in a window of some tens of requests the
order of arrivals and sizes, dealt anew by each seed, moved the served
tokens far more than the program's own run-to-run spread (PERF.md).
"""
from __future__ import annotations

import statistics

import numpy as np


def _quantiles(dist: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] + 1 - dist["min"])
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    v = np.clip(np.floor(v), dist["min"], dist["max"]).astype(np.int64)
    if "snap_to" in dist:
        ladder = np.asarray(dist["snap_to"])
        v = ladder[np.abs(v[:, None] - ladder[None, :]).argmin(axis=1)]
    return v


def size_set(mix: dict, max_len: int) -> list[tuple[int, int]]:
    """The mix's (prompt, output) sizes, the same for every seed.  A request
    never runs past ``max_len`` - 1 positions."""
    n = mix["set_size"]
    prompts = _quantiles(mix["prompt"], n)
    outs = _quantiles(mix["output"], n)
    rng = np.random.default_rng(mix["size_seed"])
    prompts = prompts[rng.permutation(n)]
    outs = outs[rng.permutation(n)]
    if prompts.max() > max_len - 2:
        raise ValueError(f"prompts of {prompts.max()} do not fit {max_len}")
    outs = np.minimum(outs, max_len - 1 - prompts)
    return [(int(p), int(o)) for p, o in zip(prompts, outs)]


def gap_set(mix: dict) -> np.ndarray:
    """The open loop's gaps between arrivals in seconds: ``set_size``
    quantiles of the exponential distribution of mean 1 / ``rate_per_s``,
    in an order fixed by the mix."""
    n = mix["set_size"]
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / mix["rate_per_s"]
    return gaps[np.random.default_rng([mix["size_seed"], 4]).permutation(n)]


class ClosedLoop:
    """Requests for ``clients`` callers.  Their k-th requests take the k-th
    block of ``clients`` sizes of the set (in an order fixed by the mix),
    so every seed sends the same sizes in every round; the seed only
    deals each block out among the clients and draws the token ids."""

    def __init__(self, mix: dict, vocab: int, max_len: int, clients: int,
                 seed: int):
        self.sizes = size_set(mix, max_len)
        self.vocab, self.clients, self.seed = vocab, clients, seed
        self.blocks = len(self.sizes) // clients
        if not self.blocks:
            raise ValueError(f"{len(self.sizes)} sizes for {clients} clients")
        self.sent = [0] * clients

    def prompt(self, i: int, length: int, stream: int = 1) -> np.ndarray:
        rng = np.random.default_rng([self.seed, stream, i])
        return rng.integers(0, self.vocab, length, dtype=np.int32)

    def next(self, client: int) -> tuple[np.ndarray, int]:
        """(prompt tokens, output tokens) of the client's next request."""
        k = self.sent[client]
        self.sent[client] += 1
        block = k % self.blocks
        deal = np.random.default_rng([self.seed, 0, k]).permutation(
            self.clients)
        p, o = self.sizes[block * self.clients + int(deal[client])]
        return self.prompt(k * self.clients + client, p), o

    def distinct_prompt_lengths(self) -> list[int]:
        return sorted({p for p, _ in self.sizes})


class OpenLoop(ClosedLoop):
    """Requests on a schedule that is the same for every seed: the j-th
    takes the j-th size of the set in the mix's order (so its k-th block
    of ``clients`` holds what the closed loop's k-th round does) and is
    sent ``gap(j - 1)`` after the one before.  The seed draws the token
    ids alone."""

    def __init__(self, mix: dict, vocab: int, max_len: int, clients: int,
                 seed: int):
        super().__init__(mix, vocab, max_len, clients, seed)
        self.gaps = gap_set(mix)

    def request(self, j: int) -> tuple[np.ndarray, int]:
        """(prompt tokens, output tokens) of the j-th request."""
        p, o = self.sizes[j % len(self.sizes)]
        return self.prompt(j, p), o

    def gap(self, j: int) -> float:
        """Seconds between the j-th request and the next."""
        return float(self.gaps[j % len(self.gaps)])
