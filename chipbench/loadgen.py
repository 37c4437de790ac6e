"""One general traffic generator, driven by a traffic file's parameters.

Copied in spirit from ``repro.serve.loadgen.generate_trace`` (seeded,
Poisson arrivals, uniform token ids) so that a change to the program cannot
move the load, and extended with what the benchmark needs:

* lengths drawn from a clipped lognormal (``median``, ``sigma``, ``min``,
  ``max``);
* the same work for every seed.  Lengths and inter-arrival gaps are the
  quantiles of their distribution at ``(i + 0.5) / block``, laid out in a
  fixed order that samples the whole distribution in every prefix (the
  bit-reversal of the quantile index), so any stretch of the traffic holds
  a fair share of short and long requests.  The order is the same for
  every seed: a window that starts from an empty pool is a transient
  whose work depends on which requests come first, so a seed may choose
  the token ids and the weights but not the sizes or the arrivals.

Traffic kinds (the ``kind`` key): ``backlog`` (every request due at 0, a
queue the window cannot drain), ``poisson`` (open loop at ``rate`` requests
per second) and ``closed_loop`` (one caller, back to back; no requests).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

@dataclasses.dataclass
class Request:
    rid: int
    due_s: float            # when the request is due, from the window start
    prompt: list[int]
    max_new: int


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of ``seed`` (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([seed & (2 ** 64 - 1), stream]))


def key_words(seed: int, stream: int) -> np.ndarray:
    """Two uint32 words for a raw threefry key: every bit of a 64-bit seed
    counts (``jax.random.PRNGKey`` keeps only the low 32)."""
    ss = np.random.SeedSequence([seed & (2 ** 64 - 1), stream])
    return ss.generate_state(2, np.uint32)


def quantile_lengths(spec: dict, count: int) -> np.ndarray:
    """``count`` lengths at the mid-quantiles of a clipped lognormal."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    z = np.array([NormalDist().inv_cdf((i + 0.5) / count)
                  for i in range(count)])
    x = np.exp(mu + sigma * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(rate: float, count: int) -> np.ndarray:
    """``count`` inter-arrival gaps at the mid-quantiles of Exp(rate)."""
    u = (np.arange(count) + 0.5) / count
    return -np.log1p(-u) / rate


def balanced_order(count: int, stride: int = 1) -> np.ndarray:
    """Indices 0..count-1 (count a power of two) in bit-reversed order, each
    first multiplied by the odd ``stride`` modulo count: every prefix of
    the order spreads over the whole range, and two strides give two
    orders that do not move together."""
    bits = count.bit_length() - 1
    if count != 1 << bits or stride % 2 == 0:
        raise ValueError(f"block {count} must be a power of two and the "
                         f"stride {stride} odd")
    rev = [int(format(i, f"0{bits}b")[::-1], 2) if bits else 0
           for i in range(count)]
    return (np.array(rev) * stride) % count


def requests(traffic: dict, seed: int, vocab: int, count: int
             ) -> list[Request]:
    """The first ``count`` requests of ``traffic``; ``seed`` draws the
    token ids."""
    kind = traffic["kind"]
    if kind not in ("backlog", "poisson"):
        raise ValueError(f"traffic kind {kind!r} makes no requests")
    block = int(traffic["block"])
    plens = quantile_lengths(traffic["prompt_len"], block)[
        balanced_order(block, 1)]
    olens = quantile_lengths(traffic["output_len"], block)[
        balanced_order(block, 5)]
    gaps = (quantile_gaps(float(traffic["rate"]), block)[
        balanced_order(block, 3)] if kind == "poisson" else np.zeros(block))
    rng = rng_for(seed, 0)
    out, due = [], 0.0
    for i in range(count):
        j = i % block
        if i:
            due += float(gaps[j])
        ids = rng.integers(1, vocab, size=int(plens[j]))
        out.append(Request(rid=i, due_s=due, prompt=[int(t) for t in ids],
                           max_new=int(olens[j])))
    return out
