"""Traffic from a data file of parameters and `--seed`.

Every seed carries the same work: lengths, gaps between arrivals and the
choice of system prompt are fixed multisets (the quantiles of the
distributions the file names). The arrival times are the file's own; the
seed permutes which lengths and which system prompt each arrival gets, and
draws the token ids. So two seeds differ by order, not by load.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lm_corpus(seed: int, n_rows: int, seq_len: int, vocab: int) -> np.ndarray:
    """`n_rows` distinct rows of `seq_len + 1` token ids: the arithmetic
    patterns of the program's `lm_synth` (start + stride * position, mod
    vocab), with (start, stride) pairs drawn without replacement so that no
    two rows are alike."""
    rng = np.random.default_rng((int(seed), 1))
    strides = 6
    if n_rows > vocab * strides:
        raise ValueError(f"{n_rows} rows do not fit {vocab * strides} patterns")
    pick = rng.choice(vocab * strides, size=n_rows, replace=False)
    starts, stride = pick // strides, pick % strides + 1
    pos = np.arange(seq_len + 1)
    return ((starts[:, None] + stride[:, None] * pos[None, :]) % vocab).astype(np.int32)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """Quantiles of a lognormal (median, sigma), clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def _zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of `n` draws each of `k` items gets under Zipf(s), by
    largest remainder."""
    w = 1.0 / np.arange(1, k + 1) ** s
    share = n * w / w.sum()
    counts = np.floor(share).astype(np.int64)
    for i in np.argsort(-(share - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts


def serve_schedule(traffic: dict, seed: int, seconds: float, vocab: int,
                   positions: int) -> dict:
    """An open-loop schedule over the pre-roll and the window.

    `vocab` and `positions` are the family's: the ids a prompt may draw
    and the longest sequence a request may reach. Returns `due` (seconds from the schedule's start, ascending), `prompts`
    (token ids), `max_new` and `counted` (due inside the window)."""
    rate = float(traffic["rate_per_s"])
    preroll = float(traffic["preroll_s"])
    span = preroll + float(seconds)
    n = max(int(round(rate * span)), 1)
    rng = np.random.default_rng((int(seed), 2))

    # Arrival times are the traffic's own, the same for every seed: Poisson
    # gaps (exponential quantiles) in an order drawn from the file's
    # `arrival_seed`. Where the bursts fall decides the tail of the first
    # token, so a seed that moved them would change the work (PERF.md §6).
    gaps = -np.log1p(-_quantiles(n)) / rate
    gaps *= span / gaps.sum()  # the multiset fills the span exactly
    order = np.random.default_rng(int(traffic["arrival_seed"])).permutation(n)
    due = np.cumsum(gaps[order])
    due -= due[0] * 0.5  # the first request does not wait a whole gap

    sysp = traffic["system_prompts"]
    sys_ids = rng.permutation(
        np.repeat(np.arange(sysp["count"]), _zipf_counts(n, sysp["count"], sysp["zipf_s"]))
    )
    user_len = rng.permutation(_lognormal_lengths(n, traffic["user_tokens"]))
    out_len = rng.permutation(_lognormal_lengths(n, traffic["output_tokens"]))
    system = rng.integers(1, vocab, size=(sysp["count"], sysp["tokens"]), dtype=np.int64)

    prompts, max_new = [], []
    for i in range(n):
        user = rng.integers(1, vocab, size=int(user_len[i]), dtype=np.int64)
        prompt = np.concatenate([system[sys_ids[i]], user]).astype(np.int32)
        new = int(min(out_len[i], positions - prompt.size))
        prompts.append(prompt)
        max_new.append(new)
    counted = (due >= preroll) & (due < span)
    return {
        "due": due, "prompts": prompts, "max_new": max_new,
        "counted": counted, "preroll_s": preroll, "span_s": span,
    }
