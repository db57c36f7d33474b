"""Traffic from a seed: ONE general generator per kind of traffic, driven
by the parameter file ``traffic/<name>.json``.

Every burst of a serving mix holds the SAME multiset of (prefix, body
length, output length) — the quantile grid of the stated distributions,
paired and ordered by the file's own ``pair_seed`` (burst k of every run
arrives in the same order) — so that no seed gets other work than
another: on the chip the order alone moved the 95th percentiles by 6%
(PERF.md, PR 23). ``--seed`` draws every token (and the weights).
"""

from __future__ import annotations

import statistics

import numpy as np


def _grid(spec: dict, n: int) -> np.ndarray:
    """The n-point quantile grid of a length distribution, clipped."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """n requests over ``count`` prefixes, Zipf(s), largest remainder."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    got = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - got))[:n - got.sum()]:
        got[i] += 1
    return got


def burst_shape(t: dict) -> list:
    """The fixed multiset of a burst: [(prefix index or None, body
    tokens, output tokens)] — the same for every seed and burst."""
    n = t["burst_requests"]
    rng = np.random.default_rng(t["pair_seed"])
    body = rng.permutation(_grid(t["body"], n))
    out = rng.permutation(_grid(t["output"], n))
    if t["prefixes"]:
        p = t["prefixes"]
        prefix = rng.permutation(np.repeat(
            np.arange(p["count"]), _zipf_counts(p["count"], p["zipf_s"], n)))
        plen = p["tokens"]
    else:
        prefix, plen = [None] * n, 0
    shape = []
    for pf, b, o in zip(prefix, body, out):
        o = min(int(o), t["total_max"] - plen - int(b))
        if o < 1:
            raise ValueError("total_max leaves a request no output token")
        shape.append((None if pf is None else int(pf), int(b), o))
    return shape


class ServeBursts:
    """Bursts of token arrays for a closed-list serve entry point."""

    def __init__(self, t: dict, seed: int, vocab: int):
        self.t, self.vocab = t, vocab
        self.rng = np.random.default_rng([int(seed), 0x5E12])
        self.shape = burst_shape(t)
        self.bursts_made = 0
        p = t["prefixes"]
        self.prefixes = ([self._tokens(p["tokens"]) for _ in range(p["count"])]
                         if p else [])

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n).astype(np.int32)

    def _requests(self, shape):
        prompts, n_new = [], []
        for pf, body, out in shape:
            toks = self._tokens(body)
            if pf is not None:
                toks = np.concatenate([self.prefixes[pf], toks])
            prompts.append(toks)
            n_new.append(out)
        return prompts, n_new

    def warmup(self):
        """The file's warm-up list, in its order: one request for every
        program shape the mix can reach."""
        return self._requests([(w["prefix"], w["body"], w["out"])
                               for w in self.t["warmup"]])

    def burst(self):
        order = np.random.default_rng(
            [self.t["pair_seed"], self.bursts_made]).permutation(
                len(self.shape))
        self.bursts_made += 1
        return self._requests([self.shape[i] for i in order])


def train_dataset(t: dict, seed: int, vocab: int) -> np.ndarray:
    """The token file a training run samples its windows from."""
    rng = np.random.default_rng([int(seed), 0x7A1])
    return rng.integers(0, vocab, size=t["dataset_tokens"]).astype(np.uint16)
