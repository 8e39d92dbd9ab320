"""One general open-loop generator, driven by a traffic file.

A traffic file (``traffic/<name>.json``) gives the arrival rate, the
prompt and output length distributions (lognormal, clipped), optional
shared system prompts with Zipf popularity, an optional backlog of
requests already due when the window opens (a server above its knee is
full from the start), and the engine's ``max_len``.

Every seed gets the same sizes and inter-arrival gaps, drawn as evenly
spaced quantiles of the stated distributions, in the same order (a fixed
shuffle); the seed draws the token ids.  So every seed does the same work
at the same times, and the spread between runs is the system's, not the
generator's: with the order drawn from the seed, which long requests fell
near the window's close changed the work done inside it.  Arrivals are
Poisson in their marginal: the gaps are the quantiles of an exponential
with the stated rate.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float              # seconds after the window opens
    prompt: np.ndarray        # (S,) int32 token ids, system prompt first
    max_new: int
    system_id: int            # -1 without a shared system prompt


ORDER_KEY = 0x10AD


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(dist: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of the clipped lognormal ``dist``."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests use each of ``count`` system prompts
    under Zipf(``s``) popularity (largest-remainder rounding)."""
    p = 1.0 / np.arange(1, count + 1) ** s
    p /= p.sum()
    raw = p * n
    out = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - out))[: n - out.sum()]:
        out[i] += 1
    return out


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_per_s"] * seconds)))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The window's arrivals, sorted by due time."""
    if mix["arrivals"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']}")
    backlog = mix.get("backlog_at_start", 0)
    n = n_requests(mix, seconds) + backlog
    rate = mix["arrivals"]["rate_per_s"]
    order = np.random.default_rng(ORDER_KEY)
    rng = np.random.default_rng([int(seed) % 2 ** 64, ORDER_KEY])
    # a backlog due at 0, then n - backlog - 1 gaps after one more arrival
    # at 0
    gaps = order.permutation(-np.log1p(-_quantiles(n - backlog - 1)) / rate)
    due = np.concatenate([np.zeros(backlog + 1), np.cumsum(gaps)])
    user = order.permutation(lognormal_sizes(mix["prompt"], n))
    out_len = order.permutation(lognormal_sizes(mix["output"], n))
    sp = mix.get("shared_prefix")
    if sp:
        sys_ids = order.permutation(np.repeat(
            np.arange(sp["count"]), zipf_counts(sp["count"], sp["zipf_s"], n)))
        sys_prompts = rng.integers(1, vocab, (sp["count"], sp["tokens"]),
                                   dtype=np.int32)
    else:
        sys_ids = np.full(n, -1)
    arrivals = []
    for i in range(n):
        body = rng.integers(1, vocab, int(user[i]), dtype=np.int32)
        prompt = body if sys_ids[i] < 0 else np.concatenate(
            [sys_prompts[sys_ids[i]], body])
        arrivals.append(Arrival(float(due[i]), prompt, int(out_len[i]),
                                int(sys_ids[i])))
    return arrivals


def prompt_range(mix: dict) -> tuple:
    """Shortest and longest prompt the mix can send."""
    sp = mix.get("shared_prefix")
    extra = sp["tokens"] if sp else 0
    return mix["prompt"]["min"] + extra, mix["prompt"]["max"] + extra


def check_fits(mix: dict, bucket=lambda n: n) -> None:
    """Every request must fit the engine's ``max_len`` whole, its prompt
    padded to the engine's ``bucket``: the engine would otherwise truncate
    the prompt or overwrite the last cache row."""
    longest = bucket(prompt_range(mix)[1]) + mix["output"]["max"]
    if longest > mix["max_len"]:
        raise ValueError(f"longest request {longest} > max_len "
                         f"{mix['max_len']}")


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of ``values``; infinities
    (requests that never answered) sort last."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    k = max(0, math.ceil(q / 100.0 * len(v)) - 1)
    return float(v[k])
