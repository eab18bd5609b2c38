"""The one traffic generator: a traffic file's parameters and a seed in,
a list of requests with due times out.

Every seed gets the same multiset of prompt lengths, output lengths and
gaps between arrivals (stratified quantiles of the file's distributions);
the seed only draws the order and the token ids. So two seeds offer the
same work and differ in its arrangement, and runs of different seeds
spread about as little as runs of one seed.

Traffic file keys (all lengths in tokens):

- ``arrivals``, one of
  - ``{"kind": "poisson", "rate_per_s": r}``: an open loop; the requests
    due in a window of ``s`` seconds are ``round(r * s)``, with
    exponential gaps;
  - ``{"kind": "onoff", "rate_per_s": r, "on_s": a, "off_s": b}``: the
    same mean rate, offered only in the first ``a`` seconds of every
    ``a + b`` (bursts at ``r * (a + b) / a``, silence between);
  - ``{"kind": "backlog", "requests": n}``: all due at 0.

  An open loop may add ``"preroll_s": p``: ``round(r * p)`` more
  arrivals, due from ``p`` seconds before the window opens, so that it
  opens on the steady state the rate holds. The harness offers them as
  part of set-up. The pre-roll and the window are drawn apart, so the
  window holds the same work for every seed.
- ``prompt`` / ``output``: ``{"dist": "lognormal", "median": m,
  "sigma": s, "min": a, "max": b}`` or ``{"dist": "uniform", "min": a,
  "max": b}``; ``"multiple": k`` rounds up to a multiple of ``k``.
  ``prompt`` may add ``"shared_prefix": {"tokens": t, "groups": g}``:
  each prompt starts with one of ``g`` prefixes of ``t`` tokens (a
  system prompt), followed by its own tokens of the drawn length.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    uid: int
    due_s: float
    prompt: np.ndarray          # (prompt_len,) int32
    max_new_tokens: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, as ints."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    v = np.clip(np.round(v), lo, hi).astype(np.int64)
    k = spec.get("multiple", 1)
    return np.minimum(-(-v // k) * k, hi)


def preroll_s(traffic: dict) -> float:
    """Seconds of arrivals offered before the window opens."""
    return float(traffic["arrivals"].get("preroll_s", 0.0))


def segments(traffic: dict, seconds: float) -> list[tuple[float, float, int]]:
    """``(start_s, end_s, requests)`` of each span drawn on its own: the
    window, and before it an open loop's pre-roll. Each span's sizes and
    gaps are stratified by themselves, so every seed puts the same work
    into the window."""
    arr = traffic["arrivals"]
    if arr["kind"] == "backlog":
        return [(0.0, 0.0, int(arr["requests"]))]
    if arr["kind"] not in ("poisson", "onoff"):
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    rate, pre = arr["rate_per_s"], preroll_s(traffic)
    out = [(-pre, 0.0, round(rate * pre))] if pre else []
    return out + [(0.0, seconds, max(1, round(rate * seconds)))]


def _on_time(arr: dict, t):
    """Seconds of offered (on) time from 0 to ``t``."""
    if arr["kind"] != "onoff":
        return t
    period = arr["on_s"] + arr["off_s"]
    k = np.floor(t / period)
    return k * arr["on_s"] + np.minimum(t - k * period, arr["on_s"])


def _wall_time(arr: dict, tau):
    """Inverse of ``_on_time``: a burst's arrivals fall in its on part."""
    if arr["kind"] != "onoff":
        return tau
    k = np.floor(tau / arr["on_s"])
    return k * (arr["on_s"] + arr["off_s"]) + (tau - k * arr["on_s"])


def due_times(traffic: dict, start: float, end: float, n: int,
              rng: np.random.Generator) -> np.ndarray:
    """``n`` due times in ``[start, end)`` seconds from the window's
    opening, sorted."""
    arr = traffic["arrivals"]
    if arr["kind"] == "backlog":
        return np.zeros(n)
    # exponential gaps at stratified quantiles, in the seed's order,
    # scaled so that the n arrivals fill the span's on-time at its rate
    a, b = _on_time(arr, start), _on_time(arr, end)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due = due * ((b - a) * (n - 0.5) / n) / max(due[-1] + gaps[-1], 1e-9)
    return _wall_time(arr, a + due)


def generate(traffic: dict, seed: int, seconds: float,
             vocab: int) -> list[Req]:
    """The requests of one run, ordered by due time."""
    rng = np.random.default_rng(seed)
    plens, olens, due = [], [], []
    for start, end, n in segments(traffic, seconds):
        plens.append(rng.permutation(_quantiles(traffic["prompt"], n)))
        olens.append(rng.permutation(_quantiles(traffic["output"], n)))
        due.append(due_times(traffic, start, end, n, rng))
    plens, olens, due = (np.concatenate(x) for x in (plens, olens, due))
    n = due.size
    share = traffic["prompt"].get("shared_prefix")
    heads, group = [np.zeros(0, np.int32)], np.zeros(n, np.int64)
    if share:
        heads = [rng.integers(0, vocab, share["tokens"], dtype=np.int32)
                 for _ in range(share["groups"])]
        group = rng.integers(0, len(heads), n)
    return [Req(uid=i, due_s=float(due[i]),
                prompt=np.concatenate([
                    heads[group[i]],
                    rng.integers(0, vocab, int(plens[i]), dtype=np.int32)]),
                max_new_tokens=int(olens[i]))
            for i in range(n)]


def shapes(traffic: dict, seconds: float, chunk: int,
           block: int = 1) -> list[int]:
    """Distinct prefill chunk lengths this traffic drives (for warm-up).
    With a shared prefix, a prompt whose prefix blocks are cached (whole
    blocks of ``block`` tokens) is prefilled from the first block after
    them, and one whose prefix is not yet cached from its start."""
    share = traffic["prompt"].get("shared_prefix")
    head = share["tokens"] if share else 0
    plens = np.concatenate([_quantiles(traffic["prompt"], n)
                            for _, _, n in segments(traffic, seconds)])
    out = set()
    for p in np.unique(plens):
        total = int(p) + head
        starts = {0}
        if head:
            starts.add(min(head // block, (total - 1) // block) * block)
        for lo in starts:
            full, rem = divmod(total - lo, chunk)
            if full:
                out.add(chunk)
            if rem:
                out.add(rem)
    return sorted(out)
