"""Token traffic: per-client permuted Zipf unigrams over the full vocabulary.

The law of the program's ``SyntheticTokenStream``, sampled in bulk: every
client has its own permutation of the vocabulary (drawn from the seed),
and each token is the client's permuted image of a Zipf(a) rank.  Round r
of a run is drawn from ``(seed, r)`` alone, so the same seed gives the
same rounds whatever else the run does.
"""
from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=4)
def zipf_cdf(vocab: int, a: float) -> np.ndarray:
    w = np.arange(1, vocab + 1, dtype=np.float64) ** (-a)
    cdf = np.cumsum(w)
    return cdf / cdf[-1]


def client_permutations(vocab: int, n_clients: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 0])
    return np.stack([rng.permutation(vocab) for _ in range(n_clients)]
                    ).astype(np.int32)


def make_rounds(traffic: dict, vocab: int, seed: int, first: int,
                count: int, perms: np.ndarray | None = None) -> np.ndarray:
    """Rounds ``first .. first+count-1`` as int32 (R, T0, n, B, L+1):
    a round's T0 steps, each client's batch of sequences of L+1 tokens
    (inputs are [..., :-1], labels [..., 1:])."""
    n, T0 = traffic["n_clients"], traffic["comm_period"]
    B, L = traffic["batch"], traffic["seq_len"]
    law = traffic["tokens"]
    if law["law"] != "zipf_permuted":
        raise ValueError(f"unknown token law {law['law']!r}")
    if perms is None:
        perms = client_permutations(vocab, n, seed)
    cdf = zipf_cdf(vocab, law["a"])
    out = np.empty((count, T0, n, B, L + 1), np.int32)
    clients = np.arange(n)[None, :, None, None]
    for i in range(count):
        u = np.random.default_rng([seed, 1, first + i]).random(
            (T0, n, B, L + 1))
        ranks = np.minimum(np.searchsorted(cdf, u, side="right"), vocab - 1)
        out[i] = perms[clients, ranks]
    return out


def round_tokens(traffic: dict) -> int:
    """Training tokens of one round: every client, step and sequence."""
    return (traffic["comm_period"] * traffic["n_clients"] * traffic["batch"]
            * traffic["seq_len"])
