"""The one traffic generator: turns a mix file (``traffic/<name>.json``)
and ``--seed`` into the requests of a run.

A mix file holds only parameters:

``loop``            ``"open"`` (Poisson arrivals at ``rate_rps``) or
                    ``"closed"`` (``clients`` callers, each sending its
                    next request when the previous one is answered).
``prefix_pool``     how many shared prefixes (documents, repositories);
                    0 means every prompt is fresh.
``prefix_tokens``   tokens of each shared prefix.
``prefix_pick``     ``"zipf"`` (with ``zipf_s``) or ``"uniform"``.
``suffix_tokens``   fresh tokens after the prefix (the question, the
                    file-local context).
``decode_tokens``   greedy tokens answered per request.
``rows``            prompt rows per request.
``sample``          answered requests the reference checks.
``fill_rows``       rows per set-up request that makes the prefixes
                    resident (a full prefill; default 8).
``shape_seed``      fixes the multiset of arrival gaps and prefix picks;
                    ``--seed`` only orders them and draws token ids, so
                    every seed offers the same work.
"""
from __future__ import annotations

import dataclasses
import json

import numpy as np

#: Token id 0 is never drawn (some tokenizers reserve it).
LOW_TOKEN = 1


def load(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    if mix["prefix_pool"] and mix["prefix_pick"] not in ("zipf", "uniform"):
        raise ValueError(f"{path}: prefix_pick must be 'zipf' or 'uniform'")
    return mix


def prompt_tokens(mix: dict) -> int:
    return (mix["prefix_tokens"] if mix["prefix_pool"] else 0) \
        + mix["suffix_tokens"]


@dataclasses.dataclass
class Plan:
    """What one run offers: the shared prefixes, then per request its
    prefix (or -1 for a fresh prompt), its tokens and, open loop, its
    scheduled arrival in seconds from the window's start."""
    prefixes: np.ndarray              # (pool, prefix_tokens) int32
    picks: np.ndarray                 # (n,) prefix index or -1
    arrivals_s: np.ndarray | None     # (n,) open loop only
    seed: int
    mix: dict
    vocab: int
    stream: int = 1                   # token stream of the requests

    def __len__(self) -> int:
        return len(self.picks)

    def tokens(self, i: int, stream: int | None = None) -> np.ndarray:
        """(rows, prompt_tokens) int32 of request ``i``, drawn from the
        seed and the request's index alone (thread order never matters).
        Another ``stream`` gives requests of the same shape that the
        window never sends."""
        rng = np.random.default_rng(
            [self.seed, self.stream if stream is None else stream, i])
        rows = self.mix["rows"]
        pick = self.picks[i % len(self.picks)]
        if pick < 0:
            return rng.integers(LOW_TOKEN, self.vocab,
                                (rows, prompt_tokens(self.mix))
                                ).astype(np.int32)
        fresh = rng.integers(LOW_TOKEN, self.vocab,
                             (rows, self.mix["suffix_tokens"]))
        pre = np.broadcast_to(self.prefixes[pick],
                              (rows, self.mix["prefix_tokens"]))
        return np.concatenate([pre, fresh], axis=1).astype(np.int32)


def _pick_multiset(mix: dict, n: int) -> np.ndarray:
    """``n`` prefix picks whose counts follow the mix's distribution
    exactly (largest remainder), independent of ``--seed``."""
    pool = mix["prefix_pool"]
    if mix["prefix_pick"] == "zipf":
        w = 1.0 / np.arange(1, pool + 1) ** mix["zipf_s"]
    else:
        w = np.ones(pool)
    share = w / w.sum() * n
    counts = np.floor(share).astype(int)
    rest = n - counts.sum()
    counts[np.argsort(-(share - counts), kind="stable")[:rest]] += 1
    return np.repeat(np.arange(pool), counts)


def build(mix: dict, vocab: int, seed: int, seconds: float) -> Plan:
    """The requests of one run of ``seconds`` seconds.

    Open loop: ``round(rate_rps * seconds)`` arrivals whose gaps are a
    fixed set of exponential draws, shuffled by the seed and scaled so
    that the last arrival falls just inside the window.  Closed loop:
    a list long enough for ``closed_max_per_client`` requests a client."""
    shape_rng = np.random.default_rng(mix["shape_seed"])
    rng = np.random.default_rng([seed, 0])
    if mix["loop"] == "open":
        n = max(int(round(mix["rate_rps"] * seconds)), 1)
        gaps = shape_rng.exponential(1.0, n)
        gaps = rng.permutation(gaps)
        arrivals = np.cumsum(gaps)
        arrivals *= seconds * (n - 0.5) / n / arrivals[-1]
    else:
        n = mix["clients"] * mix["closed_max_per_client"]
        arrivals = None
    pool = mix["prefix_pool"]
    if pool:
        # Which prefix holds which popularity rank is the seed's choice.
        rank_to_prefix = rng.permutation(pool)
        picks = rank_to_prefix[rng.permutation(_pick_multiset(mix, n))]
        # A stream of their own: the prefixes of a seed do not depend on
        # the rate or the window's length.
        prefixes = np.random.default_rng([seed, 3]).integers(
            LOW_TOKEN, vocab, (pool, mix["prefix_tokens"])).astype(np.int32)
    else:
        picks = np.full(n, -1)
        prefixes = np.zeros((0, 0), np.int32)
    return Plan(prefixes=prefixes, picks=picks.astype(np.int64),
                arrivals_s=arrivals, seed=seed, mix=mix, vocab=vocab)
