"""Single-slot prefill (``serve/model_step.make_prefill_chunk``).

The program runs a prompt chunk on one slot of the pool: one batch-1
forward of the whole chunk where every layer is attention over plain
``max_seq`` rows, a batch-1 token loop otherwise (ring-buffer windows,
recurrent state).  It is held to the program it replaced, which ran each
token through the serve step over the whole pool and kept the target
slot's rows with a ``where``: the same rows and logits for the slot, and
every other slot's rows bit for bit, a slot halfway through its own prompt
among them.  Beside it: a cached ``forward`` of S tokens against S
single-token steps and on one slot of the pool, ``Engine``'s whole-prompt
admission on a routed-expert stack, one compiled program per chunk length
whatever the slot and start, and the ``prefill_calls`` counter and the
span's ``path``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.configs.base import smoke_config
from repro.models import cache as cache_mod
from repro.models import registry as R
from repro.models import transformer as T
from repro.serve.engine import Engine, Request
from repro.serve.model_step import ModelStep, chunk_prefill

jax.config.update("jax_platform_name", "cpu")

SLOTS, MAX_SEQ = 4, 32
# attention only (chunk path), sliding-window ring leaves and recurrent
# state (serial path); the smoke windows are 16 rows, under MAX_SEQ, and
# ``ring_wide`` widens them past it: the cache is then a ring of MAX_SEQ rows
ARCHS = {"attn": "qwen3-0.6b", "ring": "gemma2-2b", "ring_wide": "gemma2-2b",
         "recurrent": "recurrentgemma-2b"}
WIDE_WINDOW = 48
DTYPES = {"f32": "float32", "bf16": "bfloat16"}
BYSTANDER = 1          # a slot halfway through its own prompt


def _cfg(arch: str, dtype: str):
    cfg = smoke_config(R.get_arch(ARCHS[arch])).with_(
        param_dtype=DTYPES[dtype], activation_dtype=DTYPES[dtype])
    if arch == "ring_wide":
        cfg = cfg.with_(pattern=tuple(
            dataclasses.replace(s, window=WIDE_WINDOW) if s.window else s
            for s in cfg.pattern))
    return cfg


def _pool_wide_serial(cfg, slots: int):
    """The replaced program: each token through the serve step over all
    slots, the pool's every leaf merged with ``where`` on the slot mask."""
    serve = R.make_serve_step(cfg)

    def merge(new, old, mask, axis):
        def f(n, o):
            shape = [1] * n.ndim
            shape[axis] = slots
            return jnp.where(mask.reshape(shape), n, o)
        return jax.tree.map(f, new, old)

    def prefill(params, cache, tokens, start, mask):
        def body(carry, tok_pos):
            cache, _ = carry
            tok, pos = tok_pos
            logits, new = serve(params, {
                "tokens": jnp.broadcast_to(tok, (slots, 1)), "cache": cache,
                "write_pos": pos})
            cache = {g: merge(new[g], cache[g], mask, ax)
                     for g, ax in (("pre", 0), ("scan", 1), ("rem", 0))}
            return (cache, logits), None
        zeros = jnp.zeros((slots, cfg.vocab), jnp.float32)
        (cache, logits), _ = jax.lax.scan(
            body, (cache, zeros), (tokens, start + jnp.arange(len(tokens))))
        return cache, logits

    return jax.jit(prefill)


@functools.cache
def _setup(arch: str, dtype: str):
    cfg = _cfg(arch, dtype)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    model = ModelStep(cfg, params, slots=SLOTS, max_seq=MAX_SEQ)
    return cfg, params, model, _pool_wide_serial(cfg, SLOTS)


def _random_pool(cache, seed: int):
    """Every row of every slot filled, so a write or a read of a row the
    program should leave alone shows."""
    leaves, tree = jax.tree.flatten(cache)
    key = jax.random.PRNGKey(seed)
    return jax.tree.unflatten(tree, [
        jax.random.normal(jax.random.fold_in(key, i), x.shape).astype(x.dtype)
        for i, x in enumerate(leaves)])


def _host(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _slot_axes(cache):
    """The slot axis of each leaf, in ``jax.tree.leaves`` order."""
    return jax.tree.leaves({g: jax.tree.map(lambda _, ax=ax: ax, cache[g])
                            for g, ax in (("pre", 0), ("scan", 1),
                                          ("rem", 0))})


def _tokens(n: int, seed: int):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


@pytest.mark.parametrize("slot", [0, 2])
@pytest.mark.parametrize("start", [0, 13])
@pytest.mark.parametrize("length", [1, 3, 8])
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("arch", ["attn", "ring", "ring_wide", "recurrent"])
def test_slot_prefill_matches_pool_wide_serial(arch, dtype, length, start,
                                               slot):
    cfg, params, model, reference = _setup(arch, dtype)
    seed = 100 * length + 10 * start + slot
    model.cache = _random_pool(model.cache, seed)
    model.prefill_rows(BYSTANDER, _tokens(3, seed + 1), 0)
    before = _host(model.cache)                # the pool is donated
    toks = _tokens(length, seed + 2)

    mask = np.zeros(SLOTS, bool)
    mask[slot] = True
    want_cache, want_logits = reference(
        params, jax.tree.unflatten(jax.tree.structure(model.cache), before),
        jnp.asarray(toks), jnp.int32(start), jnp.asarray(mask))
    got_logits = np.asarray(model.prefill_rows(slot, toks, start))
    assert int(model.pos[slot]) == start + length

    axes = _slot_axes(model.cache)
    for old, new, want, ax in zip(before, _host(model.cache),
                                  _host(want_cache), axes):
        assert new.dtype == old.dtype and new.shape == old.shape
        others = [s for s in range(SLOTS) if s != slot]
        np.testing.assert_array_equal(np.take(new, others, ax),
                                      np.take(old, others, ax))
        _assert_rows(np.take(new, slot, ax), np.take(want, slot, ax),
                     exact=arch == "attn")
    want_logits = np.asarray(want_logits)[slot]
    assert got_logits.shape == (cfg.vocab,) and got_logits.dtype == np.float32
    scale = float(np.abs(want_logits).max())
    np.testing.assert_allclose(got_logits, want_logits, rtol=0,
                               atol=LOGIT_ATOL[dtype] * scale)


# The reference runs the whole pool (four rows to a product), the program
# the slot alone, and the CPU may sum a one-row product in another order.
# A logit may then differ in its last bits (f32; measured up to 3.3e-7 of
# the largest logit), or by a bf16 rounding step carried through the layers
# (bf16; up to 3.3e-3).  The chunk path's rows come out equal; the token
# loop's by the one rounding step such a difference can flip in a bf16 row
# (measured 0.0075 relative) and in the last bits of an f32 state (6.6e-5).
LOGIT_ATOL = {"f32": 1e-6, "bf16": 1e-2}


def _assert_rows(got, want, *, exact: bool):
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32), atol=0,
            rtol=2 ** -7 if got.dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("write_pos", [0, 9])
def test_cached_forward_of_a_chunk_equals_single_token_steps(dtype,
                                                             write_pos):
    cfg = _cfg("attn", dtype)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = _random_pool(cache_mod.build_cache(cfg, 2, MAX_SEQ), 3)
    toks = jnp.asarray(_tokens(10, 4).reshape(2, 5))
    step = jax.jit(lambda c, t, p: T.forward(cfg, params, t, cache=c,
                                             write_pos=p, return_cache=True))
    whole = step(cache, toks, jnp.int32(write_pos))
    logits, c = [], cache
    for i in range(toks.shape[1]):
        out = step(c, toks[:, i:i + 1], jnp.int32(write_pos + i))
        logits.append(out.logits[:, 0])
        c = out.cache
    got = np.asarray(whole.logits, np.float32)
    want = np.asarray(jnp.stack(logits, axis=1), np.float32)
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=LOGIT_ATOL[dtype] * scale)
    for a, b in zip(_host(whole.cache), _host(c)):
        _assert_rows(a, b, exact=dtype == "f32")


@pytest.mark.parametrize("slot", [0, 2])
def test_cached_forward_on_a_pool_slot_returns_the_chunk_rows(slot):
    """``cache_slot``: the same logits as a forward over that slot's rows
    sliced out, and only the chunk's rows back; a stack with recurrent
    state refuses it."""
    cfg = _cfg("attn", "f32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    pool = _random_pool(cache_mod.build_cache(cfg, SLOTS, MAX_SEQ), 6)
    toks = jnp.asarray(_tokens(5, 7)[None])
    axes = {"pre": 0, "scan": 1, "rem": 0}
    rows = {g: jax.tree.map(lambda x, ax=ax: jnp.take(x, jnp.array([slot]),
                                                      axis=ax), pool[g])
            for g, ax in axes.items()}
    want = T.forward(cfg, params, toks, cache=rows, write_pos=jnp.int32(9),
                     return_cache=True)
    got = T.forward(cfg, params, toks, cache=pool, write_pos=jnp.int32(9),
                    return_cache=True, cache_slot=jnp.int32(slot))
    np.testing.assert_array_equal(np.asarray(got.logits),
                                  np.asarray(want.logits))
    for g, ax in axes.items():
        for new, full in zip(jax.tree.leaves(got.cache[g]),
                             jax.tree.leaves(want.cache[g])):
            np.testing.assert_array_equal(
                np.asarray(new),
                np.take(np.asarray(full), np.arange(9, 14), axis=ax + 1))

    rec = _cfg("recurrent", "f32")
    with pytest.raises(ValueError):
        T.forward(rec, T.init_params(rec, jax.random.PRNGKey(0)), toks[:, :1],
                  cache=cache_mod.build_cache(rec, SLOTS, MAX_SEQ),
                  write_pos=jnp.int32(0), return_cache=True,
                  cache_slot=jnp.int32(slot))


@pytest.mark.parametrize("slot,start,n", [(-1, 0, 2), (SLOTS, 0, 2),
                                           (0, -1, 2), (0, MAX_SEQ - 1, 2),
                                           (0, 0, 0)])
def test_prefill_rows_refuses_rows_outside_the_pool(slot, start, n):
    """The program clamps a slot or start out of range onto rows it was not
    given: the host refuses them before the call."""
    cfg = _cfg("attn", "bf16")
    model = ModelStep(cfg, T.init_params(cfg, jax.random.PRNGKey(0)),
                      slots=SLOTS, max_seq=MAX_SEQ)
    with pytest.raises(ValueError):
        model.prefill_rows(slot, _tokens(n, 0), start)
    assert model.prefill_calls == {"chunk": 0, "serial": 0}


def test_one_program_per_chunk_length_whatever_slot_and_start():
    cfg = _cfg("attn", "bf16")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    model = ModelStep(cfg, params, slots=SLOTS, max_seq=MAX_SEQ)
    for slot in range(SLOTS):
        for start in (0, 4, 11, MAX_SEQ - 4):
            model.prefill_rows(slot, _tokens(4, slot + start), start)
    assert model._prefill_one._cache_size() == 1
    model.prefill_rows(0, _tokens(3, 0), 0)
    assert model._prefill_one._cache_size() == 2


@pytest.mark.parametrize("arch,path", [("attn", "chunk"), ("ring", "serial"),
                                       ("ring_wide", "serial"),
                                       ("recurrent", "serial")])
def test_prefill_calls_and_span_report_the_path(arch, path, tmp_path):
    cfg = _cfg(arch, "bf16")
    assert chunk_prefill(cfg) == (path == "chunk")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    model = ModelStep(cfg, params, slots=2, max_seq=MAX_SEQ)
    with jax.profiler.trace(str(tmp_path)):
        model.prefill_rows(0, _tokens(4, 0), 0)
        model.prefill_rows(1, _tokens(2, 1), 0)
        model.prefill_rows(0, _tokens(1, 2), 4)
    other = {"chunk": "serial", "serial": "chunk"}[path]
    assert model.prefill_calls == {path: 3, other: 0}
    trace, = tmp_path.glob("**/*.xplane.pb")
    paths = [dict(e.stats).get("path")
             for plane in ProfileData.from_file(str(trace)).planes
             for line in plane.lines for e in line.events
             if e.name == tracing.PREFIX + "model_step.prefill_rows"]
    assert paths == [path] * 3


def test_chunk_prefill_needs_plain_attention_rows():
    assert chunk_prefill(smoke_config(R.get_arch("qwen3-0.6b")))
    # a windowed layer's cache is a ring of min(max_seq, window) rows, which
    # takes one token per call, even with a window past max_seq
    for arch in ("ring", "ring_wide"):
        cfg = _cfg(arch, "f32")
        assert not chunk_prefill(cfg)
        cache = cache_mod.build_cache(cfg, 1, MAX_SEQ)
        with pytest.raises(ValueError, match="ring-buffer"):
            T.forward(cfg, T.init_params(cfg, jax.random.PRNGKey(0)),
                      jnp.ones((1, 2), jnp.int32), cache=cache,
                      write_pos=jnp.int32(0), return_cache=True)
    for arch in ("recurrentgemma-2b", "deepseek-v2-lite-16b",
                 "whisper-large-v3", "xlstm-350m"):
        assert not chunk_prefill(smoke_config(R.get_arch(arch)))
    for arch in ("codeqwen1.5-7b", "qwen3-moe-30b-a3b"):
        assert chunk_prefill(smoke_config(R.get_arch(arch)))


def test_engine_admits_a_long_moe_prompt_exactly():
    """``Engine`` prefills a whole prompt as one chunk.  A forward of that
    many tokens would drop the tokens an expert takes past its capacity
    (here 15 for each of 8 experts, and the prompt's 96 routings send more
    to some); the program raises the capacity, so every token reaches its experts as in the token loop over
    the pool, and the slot's rows come out equal (f32)."""
    cfg = smoke_config(R.get_arch("qwen3-moe-30b-a3b")).with_(
        param_dtype="float32", activation_dtype="float32")
    assert chunk_prefill(cfg)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    max_seq, slot = 64, 1
    prompt = _tokens(MOE_PROMPT, 5)
    eng = Engine(cfg, params, slots=2, max_seq=max_seq)
    eng.active[0] = Request(rid=9, prompt=[1], max_new=1)   # slot 0 busy
    req = Request(rid=0, prompt=list(prompt), max_new=4)
    eng.submit(req)
    eng._admit()
    assert eng.active[slot] is req and int(eng.pos[slot]) == MOE_PROMPT
    assert eng.prefill_calls == {"chunk": 1, "serial": 0}

    mask = np.arange(2) == slot
    want_cache, want_logits = _pool_wide_serial(cfg, 2)(
        params, cache_mod.build_cache(cfg, 2, max_seq), jnp.asarray(prompt),
        jnp.int32(0), jnp.asarray(mask))
    want_logits = np.asarray(want_logits)[slot]
    assert req.out == [int(np.argmax(want_logits))]
    for got, want, ax in zip(_host(eng.cache), _host(want_cache),
                             _slot_axes(eng.cache)):
        _assert_rows(np.take(got, slot, ax), np.take(want, slot, ax),
                     exact=True)


MOE_PROMPT = 48
