"""Arch registry: config -> (init, train/prefill/serve steps, input_specs).

``input_specs(cfg, shape)`` returns ShapeDtypeStruct stand-ins for every model
input of a shape cell (the dry-run lowers against these; smoke tests
materialize them).  ``make_*_step`` return pure jittable functions.
"""

from __future__ import annotations

import functools
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.archs import ARCHS
from repro.configs.base import ModelCfg, ShapeCfg, shapes_for, smoke_config
from repro.models import cache as cache_mod
from repro.models import transformer as T
from repro.optim import optimizers as opt_mod


def get_arch(name: str) -> ModelCfg:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


# ---------------------------------------------------------------------------
# Input specs (abstract stand-ins, no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelCfg, shape: ShapeCfg) -> dict[str, Any]:
    """ShapeDtypeStructs for one (arch x shape) cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        text = s - (cfg.vlm.num_image_tokens if cfg.vlm else 0)
        specs = {"tokens": sds((b, text), i32), "labels": sds((b, text), i32)}
        if cfg.vlm:
            specs["img_embeds"] = sds((b, cfg.vlm.num_image_tokens,
                                       cfg.d_model), jnp.bfloat16)
        if cfg.encdec:
            specs["enc_embeds"] = sds((b, cfg.encdec.enc_seq, cfg.d_model),
                                      jnp.bfloat16)
        return specs
    if shape.kind == "prefill":
        text = s - (cfg.vlm.num_image_tokens if cfg.vlm else 0)
        specs = {"tokens": sds((b, text), i32)}
        if cfg.vlm:
            specs["img_embeds"] = sds((b, cfg.vlm.num_image_tokens,
                                       cfg.d_model), jnp.bfloat16)
        if cfg.encdec:
            specs["enc_embeds"] = sds((b, cfg.encdec.enc_seq, cfg.d_model),
                                      jnp.bfloat16)
        return specs
    # decode: one new token against a seq_len cache
    return {"tokens": sds((b, 1), i32),
            "cache": cache_mod.abstract_cache(cfg, b, s),
            "write_pos": sds((), i32)}


def materialize_inputs(cfg: ModelCfg, shape: ShapeCfg, key: jax.Array) -> dict:
    """Concrete random inputs matching input_specs (smoke tests)."""
    specs = input_specs(cfg, shape)

    def make(path, s):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        leaf_key = jax.random.fold_in(key, zlib.crc32(name.encode()) % (2**31))
        if s.dtype == jnp.int32:
            if "write_pos" in name:
                return jnp.asarray(shape.seq_len - 1, jnp.int32)
            return jax.random.randint(leaf_key, s.shape, 0, cfg.vocab,
                                      jnp.int32)
        return 0.01 * jax.random.normal(leaf_key, s.shape).astype(s.dtype)

    return jax.tree_util.tree_map_with_path(make, specs)


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelCfg, optimizer: str = "adamw",
                    lr: float = 3e-4, micro_batches: int = 1) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    micro_batches > 1 splits the batch and accumulates grads with lax.scan
    (memory: one microbatch of activations live at a time).
    """
    tx = opt_mod.get(optimizer, lr)

    def step(params, opt_state, batch):
        if micro_batches == 1:
            def loss1(p, mb):
                return T.loss_fn(cfg, T.cast_params_for_compute(cfg, p), mb)
            l, grads = jax.value_and_grad(loss1)(params, batch)
        else:
            def split(x):
                return x.reshape((micro_batches, x.shape[0] // micro_batches)
                                 + x.shape[1:])
            mbs = jax.tree.map(split, batch)

            # cast/gather params ONCE per step (outside the microbatch scan)
            # — per-microbatch gathering multiplied the ZeRO all-gather
            # volume by micro_batches (§Perf iteration 7)
            def total_loss(p, mbs):
                pc = T.cast_params_for_compute(cfg, p)

                def acc(tot, mb):
                    return tot + T.loss_fn(cfg, pc, mb), None

                tot, _ = jax.lax.scan(acc, 0.0, mbs)
                return tot / micro_batches

            l, grads = jax.value_and_grad(total_loss)(params, mbs)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree.map(jnp.add, params, updates)
        gnorm = jnp.sqrt(sum(jnp.vdot(g, g).real
                             for g in jax.tree.leaves(grads)))
        return params, opt_state, {"loss": l, "grad_norm": gnorm}

    def init_opt(params):
        return tx.init(params)

    step.init_opt = init_opt
    return step


def _final_logits(cfg, logits):
    """Serving consumers get f32 + final softcap (training applies these
    inside the vocab-parallel loss — §Perf iteration 11)."""
    from repro.models.layers import softcap
    return softcap(logits.astype(jnp.float32), cfg.final_softcap)


def make_prefill_step(cfg: ModelCfg) -> Callable:
    """(params, batch) -> (last_logits, cache)."""

    def step(params, batch):
        p = T.cast_params_for_compute(cfg, params)
        out = T.forward(cfg, p, batch["tokens"],
                        img_embeds=batch.get("img_embeds"),
                        enc_embeds=batch.get("enc_embeds"),
                        return_cache=True)
        return _final_logits(cfg, out.logits[:, -1]), out.cache

    return step


def make_serve_step(cfg: ModelCfg) -> Callable:
    """(params, batch{tokens,cache,write_pos}) -> (logits, new_cache).

    Optional batch keys ``kv_factors``/``comp_len`` carry the serving
    engine's compressed-prefix state (serve/kv_compress.py, DESIGN.md §12);
    they ride through read-only — the returned cache never contains them.
    ``cache_slot`` runs the tokens on one slot of the pool and returns only
    the new rows (``transformer.forward``)."""

    def step(params, batch):
        p = T.cast_params_for_compute(cfg, params)
        out = T.forward(cfg, p, batch["tokens"], cache=batch["cache"],
                        write_pos=batch["write_pos"],
                        kv_factors=batch.get("kv_factors"),
                        comp_len=batch.get("comp_len"),
                        cache_slot=batch.get("cache_slot"))
        return _final_logits(cfg, out.logits[:, -1]), out.cache

    return step


def step_for(cfg: ModelCfg, shape: ShapeCfg, **kw) -> Callable:
    if shape.kind == "train":
        return make_train_step(cfg, **kw)
    if shape.kind == "prefill":
        return make_prefill_step(cfg)
    return make_serve_step(cfg)


__all__ = ["ARCHS", "get_arch", "shapes_for", "smoke_config", "input_specs",
           "materialize_inputs", "make_train_step", "make_prefill_step",
           "make_serve_step", "step_for"]
