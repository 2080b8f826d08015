"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) names its loop and its
distributions; :func:`requests` turns it into a list of requests that is
a pure function of the seed.  Sizes and gaps are drawn by stratified
quantiles: each block of ``block`` requests holds the same multiset of
prompt lengths, output lengths and inter-arrival gaps, in an order the
seed permutes.  So every seed offers the same work, and seeds differ
only in order and token ids.

Distributions (each a dict with ``dist``):

- ``choice``: ``values``, equally often;
- ``loguniform``: ``low``, ``high``;
- ``lognormal``: ``median``, ``sigma``, optional ``min``/``max`` clip;
- any of them may add ``round_up_to``: a sorted list of allowed values,
  each draw rounded up to the next one and capped at the last;
- and ``block``: its own stratum size, in place of the mix's.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the client sends it."""
    prompt: np.ndarray        # int32 token ids
    max_new: int              # output tokens asked for
    due_s: float = 0.0        # open loop: offset of its arrival


def quantile(spec: dict, u: float) -> float:
    """The draw of ``spec`` at quantile ``u`` in (0, 1), before rounding."""
    d = spec["dist"]
    if d == "choice":
        vals = spec["values"]
        return float(vals[min(int(u * len(vals)), len(vals) - 1)])
    if d == "loguniform":
        lo, hi = math.log(spec["low"]), math.log(spec["high"])
        return math.exp(lo + u * (hi - lo))
    if d == "lognormal":
        z = statistics.NormalDist().inv_cdf(u)
        x = spec["median"] * math.exp(spec["sigma"] * z)
        return min(max(x, spec.get("min", x)), spec.get("max", x))
    if d == "exponential":
        return -spec["mean"] * math.log1p(-u)
    raise ValueError(f"unknown distribution {d!r}")


def draw(spec: dict, u: float) -> int:
    x = quantile(spec, u)
    steps = spec.get("round_up_to")
    if steps:
        return int(next((s for s in steps if s >= x), steps[-1]))
    return int(math.ceil(x))


def stratified(spec: dict, n: int, block: int, rng, pick=None) -> list:
    """``n`` draws: whole blocks of ``block`` quantile points (or the
    spec's own ``block``), each block permuted by ``rng``; ``pick`` maps
    a quantile to a value (:func:`draw` by default)."""
    block = int(spec.get("block", block))
    pick = pick or draw
    base = [pick(spec, (i + 0.5) / block) for i in range(block)]
    out: list = []
    while len(out) < n:
        out.extend(base[i] for i in rng.permutation(block))
    return out[:n]


def allowed_prompt_lengths(mix: dict) -> list:
    """Every prompt length the mix can send (the shapes set-up warms)."""
    spec = mix["prompt_tokens"]
    steps = spec.get("round_up_to")
    if steps:
        return sorted(set(int(s) for s in steps))
    if spec["dist"] == "choice":
        return sorted(set(int(v) for v in spec["values"]))
    raise ValueError("a served mix needs prompt lengths from a fixed set "
                     "(choice, or round_up_to)")


def requests(mix: dict, seed: int, n: int, vocab: int) -> list:
    """The first ``n`` requests of ``mix`` for ``seed``.

    Closed loop: the first ``clients`` requests ask for a residual of
    their budget, so that retirements are spread from the first tick:
    the request with the ``k``-th smallest budget keeps the share
    ``(k + 0.5) / clients`` of it, so every seed has the same residuals,
    in another order.  Open loop: ``due_s`` is the cumulative sum of
    stratified exponential gaps at ``rate_per_s``.
    """
    rng = np.random.default_rng(seed)
    # a mix with a ``schedule_seed`` replays one order of sizes and
    # gaps for every seed; the seed then draws only the token ids
    sched = (np.random.default_rng(mix["schedule_seed"])
             if "schedule_seed" in mix else rng)
    block = int(mix.get("block", 64))
    plen = stratified(mix["prompt_tokens"], n, block, sched)
    olen = stratified(mix["output_tokens"], n, block, sched)
    due = np.zeros(n)
    if mix["loop"] == "open":
        gaps = stratified({"dist": "exponential",
                           "mean": 1.0 / mix["rate_per_s"]},
                          n, block, sched, pick=quantile)
        due = np.cumsum(gaps)
    else:
        clients = min(int(mix["clients"]), n)
        order = np.argsort(olen[:clients], kind="stable")
        for k, i in enumerate(order):
            share = (k + 0.5) / clients
            olen[i] = max(1, int(math.ceil(share * olen[i])))
    out = []
    for i in range(n):
        prompt = rng.integers(2, vocab, plen[i]).astype(np.int32)
        out.append(Req(prompt, int(olen[i]), float(due[i])))
    return out
