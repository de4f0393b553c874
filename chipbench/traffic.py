"""The one general traffic generator. A traffic mix is a data file
(``traffic/<name>.json``); nothing here knows a mix by name.

Stratified, not i.i.d.: each declared distribution is replaced by its
evenly spaced quantiles, a FIXED multiset, and ``--seed`` only permutes
that multiset and draws token ids. Every seed therefore offers the
same number of arrivals, the same lengths and the same amount of work;
two runs differ in order alone. (I.i.d. draws in a 10-51 s window put
the sampling noise of ~100 requests into every metric; PR 22 was
refused for it.)

A traffic file:

``loop``      ``"open"`` (arrivals on a schedule, ``rate_per_s``) or
              ``"closed"`` (``clients`` callers, each sends its next
              request when the last one completed).
``prompt``, ``output``, ``gaps``   distributions: ``{"dist":
              "uniform"|"lognormal"|"exponential"|"fixed", ...}``.
``cycle``     how many (prompt, output) templates the multiset holds.
``standing``  open loop only: how many requests are in flight when the
              window opens (Little's law: rate x residence time, the
              arithmetic is in the file's ``why``). Closed loop: the
              client count.
``serving``   optional: keys laid over the configuration's serving
              block for this mix (``slots``: a mix is served by as
              many as its callers need).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

_GOLDEN = 0.6180339887498949


@dataclasses.dataclass(frozen=True)
class Request:
    """One request as the program will see it. ``due_s`` is seconds
    after the window opens (negative: standing population)."""

    prompt_len: int
    out_len: int
    due_s: float = 0.0


def quantiles(dist: dict, n: int) -> list[float]:
    """The ``n`` evenly spaced quantiles ``(i + 0.5) / n`` of ``dist``,
    ascending. Bounded distributions are truncated to [min, max] by
    mapping the probabilities into [F(min), F(max)], not by clipping
    (clipping would pile mass on the bounds)."""
    kind = dist["dist"]
    us = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        return [float(dist["value"])] * n
    if kind == "uniform":
        lo, hi = float(dist["min"]), float(dist["max"])
        return [lo + u * (hi - lo) for u in us]
    if kind == "exponential":
        mean = float(dist["mean"])
        raw = [-mean * math.log1p(-u) for u in us]
        # The midpoint rule loses a little of the tail's mass: rescale
        # so the multiset's mean is the declared mean EXACTLY and a
        # block of n gaps lasts exactly n * mean seconds.
        scale = mean * n / sum(raw)
        return [g * scale for g in raw]
    if kind == "lognormal":
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
        nd = NormalDist()
        f_lo = nd.cdf((math.log(float(dist["min"])) - mu) / sigma)
        f_hi = nd.cdf((math.log(float(dist["max"])) - mu) / sigma)
        return [
            math.exp(mu + sigma * nd.inv_cdf(f_lo + u * (f_hi - f_lo)))
            for u in us
        ]
    raise ValueError(f"unknown distribution {kind!r}")


def _coprime_stride(n: int) -> int:
    """A stride near n * golden ratio that is coprime with n: pairing
    prompt i with output (i * stride) % n spreads long outputs evenly
    over short and long prompts, the same way for every seed."""
    s = max(1, round(n * _GOLDEN))
    while math.gcd(s, n) != 1:
        s += 1
    return s


def templates(traffic: dict, max_total: int) -> list[tuple[int, int]]:
    """The fixed multiset of (prompt_len, out_len) pairs. No seed."""
    n = int(traffic["cycle"])
    prompts = [int(round(q)) for q in quantiles(traffic["prompt"], n)]
    outs = [int(round(q)) for q in quantiles(traffic["output"], n)]
    stride = _coprime_stride(n)
    pairs = []
    for i, p in enumerate(prompts):
        o = outs[(i * stride) % n]
        if p + o > max_total:
            raise ValueError(
                f"template {i}: prompt {p} + output {o} > {max_total}"
            )
        pairs.append((p, o))
    return pairs


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def template_stream(pairs, seed: int):
    """Endless seed-permuted walk over the multiset: every block of
    ``len(pairs)`` requests is one permutation of all of them."""
    rng = _rng(seed, 1)
    while True:
        for i in rng.permutation(len(pairs)):
            yield pairs[int(i)]


def open_schedule(traffic: dict, pairs, seed: int, horizon_s: float):
    """Arrivals of an open loop, due times in seconds after the window
    opens, up to ``horizon_s``. Gaps are the stratified quantiles of
    the declared gap distribution with mean 1 / rate, permuted in
    blocks of ``gap_block``: every block lasts exactly block / rate
    seconds whatever the seed."""
    rate = float(traffic["rate_per_s"])
    block = int(traffic.get("gap_block", 16))
    gaps = quantiles({**traffic["gaps"], "mean": 1.0 / rate}, block)
    rng = _rng(seed, 2)
    stream = template_stream(pairs, seed)
    out, t = [], 0.0
    while True:
        for i in rng.permutation(block):
            t += gaps[int(i)]
            if t >= horizon_s:
                return out
            p, o = next(stream)
            out.append(Request(p, o, t))


def standing_population(pairs, count: int) -> list[Request]:
    """The requests in flight when the window opens, as ordinary
    requests: the prompt already carries the history a request of that
    age has (prompt + tokens so far), the output is what remains. No
    seed: the same shapes in every run, so set-up compiles the same
    programs. Little's law picks WHICH requests are in flight: a
    request is in the system for a time proportional to its output, so
    templates are taken at the evenly spaced quantiles of the
    output-weighted multiset; ages are spread evenly over (0, 1) by a
    golden-ratio sequence, uncorrelated with length."""
    order = sorted(range(len(pairs)), key=lambda i: (pairs[i][1], i))
    weights = np.cumsum([pairs[i][1] for i in order], dtype=np.float64)
    out = []
    for j in range(count):
        k = int(np.searchsorted(weights, (j + 0.5) / count * weights[-1]))
        p, o = pairs[order[min(k, len(order) - 1)]]
        age = ((j + 0.5) * _GOLDEN) % 1.0
        done = min(int(age * o), o - 1)
        out.append(Request(p + done, o - done, due_s=-1.0))
    return out


def token_ids(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index``: distinct per request, so no two
    prompts share a prefix page."""
    return _rng(seed, 1000 + index).integers(0, vocab, size=n, dtype=np.int32)
