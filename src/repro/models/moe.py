"""Top-k routed Mixture-of-Experts with sort-based capacity dispatch.

Dispatch is scatter/gather based (GShard semantics, megablocks-style layout):
no (tokens x experts x capacity) one-hot tensor is ever built — at 128
experts / top-8 that tensor would be ~40 G elements.  Instead token-choice
pairs are sorted by expert id, positioned within their expert via a running
count, dropped past the static capacity, and moved through an (E, C, D)
buffer:

  tokens (N, D) --gather--> (E, C, D) --batched FFN--> (E, C, D) --scatter-add--> (N, D)

Sharding: expert dimension E -> "model" (expert parallelism); the gather /
scatter across the token dimension becomes the dispatch/combine all-to-all
under SPMD.  Router runs in f32.  Gradients flow through the combine weights
(router learns) and the expert FFN; the integer routing itself is
non-differentiable as usual.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import activation


def capacity(n_tokens: int, num_experts: int, top_k: int,
             capacity_factor: float) -> int:
    c = int(n_tokens * top_k * capacity_factor / num_experts)
    return max(8, min(c, n_tokens))


def _dispatch_ffn_combine(cfg, tokens, logits, wg, wu, wd, *, e_start, e_local,
                          cap):
    """Sort-based dispatch of ``tokens`` (N, D) to experts
    [e_start, e_start+e_local), batched FFN, weighted combine -> (N, D).

    Used by both the single-device path (e_start=0, e_local=E) and the
    expert-parallel shard_map path (each model shard owns e_local experts
    and only its own tokens; combine is psum'd by the caller).
    """
    mcfg = cfg.moe
    dt = tokens.dtype
    n, d = tokens.shape
    k = mcfg.top_k
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if mcfg.norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    flat_expert = experts.reshape(-1)                       # (N*k,) global ids
    flat_token = jnp.repeat(jnp.arange(n), k)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_expert)                        # stable
    se, stok, sgate = flat_expert[order], flat_token[order], flat_gate[order]
    within = jnp.arange(n * k) - jnp.searchsorted(se, se, side="left")
    local_e = se - e_start
    keep = (within < cap) & (local_e >= 0) & (local_e < e_local)
    slot = jnp.where(keep, local_e * cap + within, e_local * cap)

    src = jnp.full((e_local * cap,), n, dtype=jnp.int32)    # n = OOB pad row
    src = src.at[slot].set(stok.astype(jnp.int32), mode="drop")
    tok_pad = jnp.concatenate([tokens, jnp.zeros((1, d), dt)], axis=0)
    xe = tok_pad[src].reshape(e_local, cap, d)              # (E_loc, C, D)

    h = activation(cfg.act, jnp.einsum("ecd,edf->ecf", xe, wg))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wu)
    ye = jnp.einsum("ecf,efd->ecd", h, wd).reshape(e_local * cap, d)

    ye_pad = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)
    contrib = ye_pad[jnp.where(keep, slot, e_local * cap)] \
        * sgate[:, None].astype(dt)
    return jnp.zeros((n, d), dt).at[stok].add(
        jnp.where(keep[:, None], contrib, 0))


def moe_block(cfg, p, x):
    """x: (B, S, D) -> (B, S, D).  Config from cfg.moe.

    With an active mesh: expert-parallel shard_map — every device routes its
    LOCAL tokens, dispatches to its model-shard's experts, and a small
    (N_loc, D) psum over `model` combines.  Without this, XLA's partitioning
    of the cross-sharded dispatch gather all-gathers every token globally
    (~3.5 TB/step/device on qwen3-moe train_4k; EXPERIMENTS.md §Perf
    iteration 5)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.activation import _resolve, get_mesh
    mcfg = cfg.moe
    dt = x.dtype
    b, s, d = x.shape
    e, k = mcfg.num_experts, mcfg.top_k

    mesh = get_mesh()
    ep = (mesh is not None and "model" in mesh.axis_names
          and e % mesh.shape["model"] == 0)
    if ep:
        ba = _resolve(mesh, "batch")
        n_model = mesh.shape["model"]
        e_local = e // n_model
        dp = mesh.size // n_model
        n_loc = max(1, b * s // dp)
        cap = capacity(n_loc, e, k, mcfg.capacity_factor)

        def fn(xl, router, wg, wu, wd):
            bl, sl, _ = xl.shape
            toks = xl.reshape(bl * sl, d)
            logits = jnp.dot(toks.astype(jnp.float32),
                             router.astype(jnp.float32))
            e0 = jax.lax.axis_index("model") * e_local
            out = _dispatch_ffn_combine(cfg, toks, logits, wg, wu, wd,
                                        e_start=e0, e_local=e_local, cap=cap)
            out = jax.lax.psum(out, "model")
            return out.reshape(bl, sl, d)

        out = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(ba, None, None), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(ba, None, None), check_vma=False,
        )(x, p["moe/router"], p["moe/w_gate"].astype(dt),
          p["moe/w_up"].astype(dt), p["moe/w_down"].astype(dt))
    else:
        n = b * s
        tokens = x.reshape(n, d)
        logits = jnp.dot(tokens.astype(jnp.float32),
                         p["moe/router"].astype(jnp.float32))
        cap = capacity(n, e, k, mcfg.capacity_factor)
        out = _dispatch_ffn_combine(
            cfg, tokens, logits, p["moe/w_gate"].astype(dt),
            p["moe/w_up"].astype(dt), p["moe/w_down"].astype(dt),
            e_start=0, e_local=e, cap=cap)

    out = out.reshape(b, s, d)

    # --- Shared experts (deepseek): dense MLP always on ---
    if mcfg.num_shared:
        tokens = x.reshape(b * s, d)
        gate = jnp.dot(tokens, p["moe/shared/w_gate"].astype(dt))
        up = jnp.dot(tokens, p["moe/shared/w_up"].astype(dt))
        shared = jnp.dot(activation(cfg.act, gate) * up,
                         p["moe/shared/w_down"].astype(dt))
        out = out + shared.reshape(b, s, d)

    return out


def aux_load_balance_loss(logits_f32: jax.Array, experts: jax.Array,
                          num_experts: int) -> jax.Array:
    """Switch-style load-balance auxiliary loss (exposed for the train loop)."""
    probs = jax.nn.softmax(logits_f32, axis=-1)
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(experts[..., 0], num_experts)
    ce = jnp.mean(one_hot, axis=0)
    return num_experts * jnp.sum(me * ce)
