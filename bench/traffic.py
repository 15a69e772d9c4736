"""The traffic generator: one function per kind, driven by a mix's data.

Every length, arrival gap and prefix choice is drawn by stratified
sampling: the i-th of n values is the distribution's quantile at
(i + 0.5) / n, and the seed only permutes them.  So every seed sends the
same set of sizes and arrivals in another order, and differs from
another seed in the order and in the token ids alone.  Token ids are
drawn from [1, vocab): id 0 is the engine's pad.

Kinds:

- ``closed_loop``: ``clients`` clients, each sending its next request
  when the last one finished; ``requests_per_client`` each, in rounds
  that each hold one request of every stratum of the lengths.
- ``open_loop``: Poisson arrivals at ``rate_per_s``, from ``lead_in_s``
  before the window to ``grace_s`` after it, as many in the window for
  every seed; each prompt may start with
  one of ``shared_prefixes.count`` prefixes of ``len`` tokens, chosen
  with Zipf popularity of exponent ``zipf_s``.
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np


@dataclasses.dataclass
class Item:
    prompt: list[int]
    out_len: int
    client: int = -1     # closed loop: the client that sends it
    due: float = 0.0     # open loop: seconds after the arrivals start
    prefix: int = -1     # index of the shared prefix it starts with


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(d: dict, n: int) -> np.ndarray:
    """n lengths of a lognormal (median, sigma) clipped to [min, max], in
    ascending order."""
    nd = statistics.NormalDist()
    z = np.asarray([nd.inv_cdf(q) for q in quantiles(n)])
    x = d["median"] * np.exp(d["sigma"] * z)
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


def lognormal_lengths(d: dict, n: int, rng) -> np.ndarray:
    return rng.permutation(lognormal_quantiles(d, n))


def by_rounds(d: dict, clients: int, rounds: int, rng) -> np.ndarray:
    """(rounds, clients) lengths: the clients x rounds quantiles cut into
    ``clients`` strata of neighbours, and each round given one length of
    every stratum.  So every round, and so every window that sees the
    first few rounds, holds the same spread of sizes for every seed."""
    strata = lognormal_quantiles(d, clients * rounds).reshape(clients, rounds)
    out = np.stack([rng.permutation(row) for row in strata], axis=1)
    return np.stack([rng.permutation(r) for r in out])


def poisson_arrivals(rate: float, edges, rng) -> np.ndarray:
    """Arrival times of a Poisson process over the segments between
    ``edges``: each segment of length d gets round(rate * d) arrivals,
    its stratified exponential gaps permuted and scaled to fill it, so
    every seed puts as many arrivals in each segment."""
    out = []
    for a, b in zip(edges, edges[1:]):
        n = round(rate * (b - a))
        if n:
            gaps = rng.permutation(-np.log1p(-quantiles(n)))
            start = np.cumsum(gaps) - gaps   # the first arrival at a
            out.append(a + start * (b - a) / gaps.sum())
    return np.concatenate(out) if out else np.zeros(0)


def zipf_choices(k: int, s: float, n: int, rng) -> np.ndarray:
    """n picks among k items, item i in proportion to 1 / (i + 1)^s."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    return rng.permutation(np.repeat(np.arange(k), counts))


def _tokens(rng, n: int, vocab: int) -> list[int]:
    return rng.integers(1, vocab, n).tolist()


def closed_loop(mix: dict, vocab: int, rng) -> list[Item]:
    c, rounds = mix["clients"], mix["requests_per_client"]
    plen = by_rounds(mix["prompt_len"], c, rounds, rng)
    olen = by_rounds(mix["output_len"], c, rounds, rng)
    return [
        Item(_tokens(rng, int(plen[r, k]), vocab), int(olen[r, k]), client=k)
        for r in range(rounds) for k in range(c)
    ]


def open_loop(mix: dict, vocab: int, rng, seconds: float) -> list[Item]:
    lead = mix["lead_in_s"]
    edges = [0.0, lead, lead + seconds, lead + seconds + mix["grace_s"]]
    due = poisson_arrivals(mix["rate_per_s"], edges, rng)
    n = len(due)
    ulen = lognormal_lengths(mix["prompt_len"], n, rng)
    olen = lognormal_lengths(mix["output_len"], n, rng)
    sp = mix.get("shared_prefixes")
    if sp:
        prefixes = [_tokens(rng, sp["len"], vocab) for _ in range(sp["count"])]
        pick = zipf_choices(sp["count"], sp["zipf_s"], n, rng)
    items = []
    for i in range(n):
        head, which = ([], -1) if not sp else (prefixes[pick[i]], int(pick[i]))
        items.append(Item(head + _tokens(rng, int(ulen[i]), vocab),
                          int(olen[i]), due=float(due[i]), prefix=which))
    return items


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> list[Item]:
    rng = np.random.default_rng(seed)
    if mix["kind"] == "closed_loop":
        return closed_loop(mix, vocab, rng)
    if mix["kind"] == "open_loop":
        return open_loop(mix, vocab, rng, seconds)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def prompt_range(mix: dict) -> tuple[int, int]:
    """Shortest and longest prompt the mix can send."""
    head = mix.get("shared_prefixes", {}).get("len", 0)
    return head + mix["prompt_len"]["min"], head + mix["prompt_len"]["max"]
