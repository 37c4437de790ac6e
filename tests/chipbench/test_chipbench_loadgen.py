"""The traffic generator and the latency arithmetic."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import loadgen, stats

TRAFFIC = Path(__file__).resolve().parents[2] / "chipbench" / "traffic"
BIG_SEED = 2 ** 40 + 12345


# the generator's open-loop kind, which no cell uses yet
POISSON = {"kind": "poisson", "rate": 0.4, "block": 32, "requests": 200,
           "prompt_len": {"median": 48, "sigma": 0.8, "min": 8, "max": 256},
           "output_len": {"median": 32, "sigma": 0.8, "min": 4, "max": 128}}


def _load(name):
    if name == "poisson":
        return POISSON
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", ["chat-backlog", "poisson"])
def test_requests_are_seeded(mix):
    tr = _load(mix)
    a = loadgen.requests(tr, BIG_SEED, 151936, 100)
    b = loadgen.requests(tr, BIG_SEED, 151936, 100)
    c = loadgen.requests(tr, BIG_SEED + 1, 151936, 100)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("mix", ["chat-backlog", "poisson"])
def test_every_seed_gets_the_same_work(mix):
    """Sizes and arrivals are the same for every seed, and each block holds
    every quantile of the length distribution once."""
    tr = _load(mix)
    block = tr["block"]
    runs = [loadgen.requests(tr, s, 1000, 3 * block)
            for s in (1, 2 ** 33 + 7)]
    shape = [[(len(r.prompt), r.max_new, r.due_s) for r in reqs]
             for reqs in runs]
    assert shape[0] == shape[1]
    first = sorted(len(r.prompt) for r in runs[0][:block])
    assert first == sorted(loadgen.quantile_lengths(tr["prompt_len"], block))
    lengths = [len(r.prompt) for r in runs[0]]
    lo, hi = tr["prompt_len"]["min"], tr["prompt_len"]["max"]
    assert min(lengths) >= lo and max(lengths) <= hi
    assert all(0 < t < 1000 for r in runs[0] for t in r.prompt)
    dues = [r.due_s for r in runs[0]]
    if tr["kind"] == "poisson":
        assert dues[0] == 0 and all(b > a for a, b in zip(dues, dues[1:]))
    else:
        assert set(dues) == {0.0}


def test_balanced_order_spreads_every_prefix():
    order = loadgen.balanced_order(32, 1)
    assert sorted(order) == list(range(32))
    for k in (2, 4, 8, 16):             # each prefix hits every 32/k-th bin
        assert sorted(order[:k] // (32 // k)) == list(range(k))
    assert list(loadgen.balanced_order(32, 5)) != list(order)
    with pytest.raises(ValueError):
        loadgen.balanced_order(12)


def test_lognormal_quantiles_and_poisson_rate():
    spec = {"median": 128, "sigma": 0.8, "min": 16, "max": 640}
    q = loadgen.quantile_lengths(spec, 33)
    assert q[16] == 128                  # the middle quantile is the median
    assert list(q) == sorted(q)
    gaps = loadgen.quantile_gaps(2.0, 1000)
    assert np.mean(gaps) == pytest.approx(0.5, rel=0.01)


def test_rsvd_loop_is_seeded():
    """The closed loop's inputs: resident matrices and one key per call,
    all from the seed."""
    import jax.numpy as jnp
    from chipbench.references import rsvd as ref
    tr = _load("rsvd-loop")
    with pytest.raises(ValueError, match="makes no requests"):
        loadgen.requests(tr, 1, 10, 5)

    def inputs(seed):
        mats = ref.paper_matrices(jnp.asarray(loadgen.key_words(seed, 0)),
                                  n=32, rank=4, s_p=1e-4, count=2)
        keys = [loadgen.key_words(seed, 1000 + i) for i in range(3)]
        return [np.asarray(m) for m in mats], keys

    (m1, k1), (m2, k2), (m3, k3) = (inputs(BIG_SEED), inputs(BIG_SEED),
                                    inputs(BIG_SEED + 1))
    assert all((a == b).all() for a, b in zip(m1, m2))
    assert [list(k) for k in k1] == [list(k) for k in k2]
    assert not (m1[0] == m3[0]).all()
    assert [list(k) for k in k1] != [list(k) for k in k3]
    assert len({tuple(k) for k in k1}) == 3


def test_key_words_keep_every_bit_of_the_seed():
    a = loadgen.key_words(2 ** 33 + 1, 0)
    b = loadgen.key_words(1, 0)
    assert a.dtype == np.uint32 and a.shape == (2,)
    assert list(a) != list(b)


def test_percentile_is_the_serving_stacks_and_empty_raises():
    from repro.serve.metrics import percentile as program_percentile
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 1001):
        xs = list(rng.exponential(size=n))
        for p in (50, 90, 95, 99):
            assert stats.percentile(xs, p) == program_percentile(xs, p)
    assert stats.percentile([5.0, 1.0, 4.0, 2.0, 3.0], 50) == 3.0
    with pytest.raises(stats.EmptySampleError):
        stats.percentile([], 95)
