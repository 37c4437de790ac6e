"""Pattern-scanned transformer: schema, init, forward, loss.

Params are a flat dict ``{"path/like/this": array}``:

  * ``layers/p{i}/...`` — pattern position i of the scanned group; leaves have
    a leading ``n_scan_periods`` dim and are consumed by ``lax.scan`` so the
    lowered HLO is O(period), not O(n_layers).
  * ``rem{j}/...`` — the n_layers % period remainder layers, unrolled.
  * ``enc/...`` — encoder stack (whisper), ``embed/...``, ``final_norm/...``,
    ``unembed`` (absent when tied).

Caches mirror this structure: {"scan": (c_p0, ...), "rem": (c_r0, ...),
"enc_kv": ...} with scan leaves stacked over periods.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelCfg
from repro.models import layers as L
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.sharding.activation import constrain


# ---------------------------------------------------------------------------
# Parameter schema: shapes + logical axes, one place for init/abstract/specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]   # logical axes, same length as shape
    scale: float = 0.02               # init std (0 -> zeros, -1 -> ones*0)


def _norm_defs(cfg, prefix) -> dict[str, ParamDef]:
    d = {f"{prefix}/scale": ParamDef((cfg.d_model,), (None,), 0.0)}
    if cfg.norm == "layernorm":
        d[f"{prefix}/bias"] = ParamDef((cfg.d_model,), (None,), 0.0)
    return d


def _layer_defs(cfg: ModelCfg, spec: LayerSpec) -> dict[str, ParamDef]:
    D, H, KV, hd, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                       cfg.d_ff)
    s_in = 0.02
    s_out = 0.02 / math.sqrt(2 * cfg.n_layers)
    defs: dict[str, ParamDef] = {}
    defs.update(_norm_defs(cfg, "norm1"))
    if not cfg.parallel_block and spec.ffn != "none":
        defs.update(_norm_defs(cfg, "norm2"))
    if cfg.post_norms:
        defs.update(_norm_defs(cfg, "norm1_post"))
        defs.update(_norm_defs(cfg, "norm2_post"))

    if spec.mixer == "attn":
        defs["attn/wq"] = ParamDef((D, H, hd), ("embed", "heads", None), s_in)
        defs["attn/wk"] = ParamDef((D, KV, hd), ("embed", "heads", None), s_in)
        defs["attn/wv"] = ParamDef((D, KV, hd), ("embed", "heads", None), s_in)
        defs["attn/wo"] = ParamDef((H * hd, D), ("heads", "embed"), s_out)
        if cfg.qkv_bias:
            defs["attn/bq"] = ParamDef((H, hd), ("heads", None), 0.0)
            defs["attn/bk"] = ParamDef((KV, hd), ("heads", None), 0.0)
            defs["attn/bv"] = ParamDef((KV, hd), ("heads", None), 0.0)
        if cfg.qk_norm:
            defs["attn/q_norm"] = ParamDef((hd,), (None,), 0.0)
            defs["attn/k_norm"] = ParamDef((hd,), (None,), 0.0)
    elif spec.mixer == "mla":
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        defs["mla/wq"] = ParamDef((D, H, qk), ("embed", "heads", None), s_in)
        defs["mla/w_dkv"] = ParamDef((D, m.kv_lora_rank), ("embed", None), s_in)
        defs["mla/kv_norm"] = ParamDef((m.kv_lora_rank,), (None,), 0.0)
        defs["mla/w_kr"] = ParamDef((D, m.qk_rope_dim), ("embed", None), s_in)
        defs["mla/w_uk"] = ParamDef((m.kv_lora_rank, H, m.qk_nope_dim),
                                    (None, "heads", None), s_in)
        defs["mla/w_uv"] = ParamDef((m.kv_lora_rank, H, m.v_head_dim),
                                    (None, "heads", None), s_in)
        defs["mla/wo"] = ParamDef((H * m.v_head_dim, D), ("heads", "embed"),
                                  s_out)
    elif spec.mixer == "rglru":
        Dr = cfg.rnn.d_rnn or D
        W = cfg.rnn.conv_width
        defs["rnn/w_in"] = ParamDef((D, Dr), ("embed", "inner"), s_in)
        defs["rnn/w_gate_in"] = ParamDef((D, Dr), ("embed", "inner"), s_in)
        defs["rnn/conv_w"] = ParamDef((W, Dr), (None, "inner"), 0.3)
        defs["rnn/w_a"] = ParamDef((Dr, Dr), ("inner", "inner2"), s_in)
        defs["rnn/w_x"] = ParamDef((Dr, Dr), ("inner", "inner2"), s_in)
        defs["rnn/lam"] = ParamDef((Dr,), ("inner",), 0.5)
        defs["rnn/w_out"] = ParamDef((Dr, D), ("inner", "embed"), s_out)
    elif spec.mixer == "mlstm":
        Di = int(cfg.rnn.mlstm_proj_factor * D)
        W = cfg.rnn.conv_width
        defs["mlstm/w_up"] = ParamDef((D, Di), ("embed", "inner"), s_in)
        defs["mlstm/w_z"] = ParamDef((D, Di), ("embed", "inner"), s_in)
        defs["mlstm/conv_w"] = ParamDef((W, Di), (None, "inner"), 0.3)
        defs["mlstm/wq"] = ParamDef((Di, Di), ("inner", "inner2"), s_in)
        defs["mlstm/wk"] = ParamDef((Di, Di), ("inner", "inner2"), s_in)
        defs["mlstm/wv"] = ParamDef((Di, Di), ("inner", "inner2"), s_in)
        defs["mlstm/w_ig"] = ParamDef((Di, cfg.n_heads), ("inner", None), s_in)
        defs["mlstm/w_fg"] = ParamDef((Di, cfg.n_heads), ("inner", None), s_in)
        defs["mlstm/w_down"] = ParamDef((Di, D), ("inner", "embed"), s_out)
    elif spec.mixer == "slstm":
        hd_s = D // cfg.n_heads
        defs["slstm/w_x"] = ParamDef((D, 4 * D), ("embed", "inner"), s_in)
        defs["slstm/r"] = ParamDef((cfg.n_heads, hd_s, 4 * hd_s),
                                   ("heads", None, None), s_in)
        defs["slstm/w_out"] = ParamDef((D, D), ("inner", "embed"), s_out)
    else:
        raise ValueError(spec.mixer)

    if spec.cross_attn:
        defs["xattn/wq"] = ParamDef((D, H, hd), ("embed", "heads", None), s_in)
        defs["xattn/wk"] = ParamDef((D, KV, hd), ("embed", "heads", None), s_in)
        defs["xattn/wv"] = ParamDef((D, KV, hd), ("embed", "heads", None), s_in)
        defs["xattn/wo"] = ParamDef((H * hd, D), ("heads", "embed"), s_out)
        defs.update(_norm_defs(cfg, "norm_x"))

    if spec.ffn == "mlp":
        defs["mlp/w_gate"] = ParamDef((D, F), ("embed", "mlp"), s_in)
        defs["mlp/w_up"] = ParamDef((D, F), ("embed", "mlp"), s_in)
        defs["mlp/w_down"] = ParamDef((F, D), ("mlp", "embed"), s_out)
    elif spec.ffn == "moe":
        mc = cfg.moe
        defs["moe/router"] = ParamDef((D, mc.num_experts), ("embed", None),
                                      s_in)
        defs["moe/w_gate"] = ParamDef((mc.num_experts, D, mc.d_expert),
                                      ("expert", "embed", None), s_in)
        defs["moe/w_up"] = ParamDef((mc.num_experts, D, mc.d_expert),
                                    ("expert", "embed", None), s_in)
        defs["moe/w_down"] = ParamDef((mc.num_experts, mc.d_expert, D),
                                      ("expert", None, "embed"), s_out)
        if mc.num_shared:
            Fs = mc.d_shared or mc.d_expert * mc.num_shared
            defs["moe/shared/w_gate"] = ParamDef((D, Fs), ("embed", "mlp"), s_in)
            defs["moe/shared/w_up"] = ParamDef((D, Fs), ("embed", "mlp"), s_in)
            defs["moe/shared/w_down"] = ParamDef((Fs, D), ("mlp", "embed"), s_out)
    return defs


def schema(cfg: ModelCfg) -> dict[str, ParamDef]:
    """Full parameter schema: path -> ParamDef."""
    defs: dict[str, ParamDef] = {}
    defs["embed/tokens"] = ParamDef((cfg.vocab, cfg.d_model),
                                    ("vocab", "embed"), 1.0)
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((cfg.d_model, cfg.vocab),
                                   ("embed", "vocab"), 0.02)
    defs.update(_norm_defs(cfg, "final_norm"))
    if cfg.vlm:
        defs["vlm/proj"] = ParamDef((cfg.d_model, cfg.d_model),
                                    ("embed", "embed2"), 0.02)

    # unrolled prelude layers (deepseek's dense layer 0)
    for j, spec in enumerate(cfg.prelude):
        for k, d in _layer_defs(cfg, spec).items():
            defs[f"pre{j}/{k}"] = d
    # scanned group: leading n_scan_periods dim, logical axis "layers"
    if cfg.n_scan_periods:
        for i, spec in enumerate(cfg.pattern):
            for k, d in _layer_defs(cfg, spec).items():
                defs[f"layers/p{i}/{k}"] = ParamDef(
                    (cfg.n_scan_periods,) + d.shape, ("layers",) + d.axes,
                    d.scale)
    for j in range(cfg.n_remainder):
        spec = cfg.pattern[j % cfg.period]
        for k, d in _layer_defs(cfg, spec).items():
            defs[f"rem{j}/{k}"] = d

    # encoder stack (whisper): homogeneous dense layers, scanned
    if cfg.encdec:
        enc_spec = LayerSpec(mixer="attn", ffn="mlp")
        for k, d in _layer_defs(cfg, enc_spec).items():
            defs[f"enc/layers/p0/{k}"] = ParamDef(
                (cfg.encdec.enc_layers,) + d.shape, ("layers",) + d.axes,
                d.scale)
        defs.update({f"enc/{k}": v for k, v in _norm_defs(cfg, "final_norm").items()})
    return defs


def init_params(cfg: ModelCfg, key: jax.Array) -> dict[str, jax.Array]:
    defs = schema(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    params = {}
    keys = jax.random.split(key, len(defs))
    for k_rng, (name, d) in zip(keys, sorted(defs.items())):
        if d.scale == 0.0:
            params[name] = jnp.zeros(d.shape, dtype)
        else:
            params[name] = (d.scale * jax.random.normal(
                k_rng, d.shape, jnp.float32)).astype(dtype)
    return params


def abstract_params(cfg: ModelCfg) -> dict[str, jax.ShapeDtypeStruct]:
    dtype = jnp.dtype(cfg.param_dtype)
    return {name: jax.ShapeDtypeStruct(d.shape, dtype)
            for name, d in schema(cfg).items()}


def param_count(cfg: ModelCfg) -> int:
    return sum(math.prod(d.shape) for d in schema(cfg).values())


def active_param_count(cfg: ModelCfg) -> int:
    """Active params per token (MoE: top_k of num_experts experts)."""
    total = 0
    for name, d in schema(cfg).items():
        n = math.prod(d.shape)
        if cfg.moe and "/moe/w_" in name and "shared" not in name:
            n = n * cfg.moe.top_k // cfg.moe.num_experts
        total += n
    return total


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def sub(d: dict[str, Any], prefix: str) -> dict[str, Any]:
    return {k[len(prefix):]: v for k, v in d.items() if k.startswith(prefix)}


def _act_dtype(cfg):
    return jnp.dtype(cfg.activation_dtype)


# ---------------------------------------------------------------------------
# One layer
# ---------------------------------------------------------------------------

def apply_layer(cfg: ModelCfg, spec: LayerSpec, p: dict, x: jax.Array, *,
                positions, cache, write_pos, enc_out, return_cache: bool,
                causal: bool = True, factors=None, comp_len=None,
                cache_slot=None):
    """Residual block: norm -> mixer -> (+) [norm -> ffn -> (+)].
    Returns (x, new_cache_dict_or_None).  ``factors``/``comp_len`` carry the
    serving engine's compressed-prefix state (DESIGN.md §12) — None/empty
    for every non-serving path; ``cache_slot``: see ``forward``."""
    x = constrain(x, "batch", None, None)   # re-anchor the residual stream
    h = L.apply_norm(cfg, p, "norm1", x)
    new_cache: dict[str, Any] = {}

    if spec.mixer == "attn":
        c = None
        if cache is not None and "k" in cache:
            c = L.KVCache(cache["k"], cache["v"])
        mix, kv = _attn_with_cache(cfg, spec, p, h, positions=positions,
                                   cache=c, write_pos=write_pos,
                                   return_cache=return_cache, causal=causal,
                                   factors=factors, comp_len=comp_len,
                                   cache_slot=cache_slot)
        if kv is not None:
            new_cache.update({"k": kv.k, "v": kv.v})
    elif cache_slot is not None:
        raise ValueError(f"a pool slot's rows are attention rows, not the "
                         f"{spec.mixer!r} mixer's state")
    elif spec.mixer == "mla":
        mix, c = mla_mod.mla_block(cfg, p, h, positions=positions,
                                   cache=cache if cache and "ckv" in cache else None,
                                   write_pos=write_pos,
                                   return_cache=return_cache)
        if c:
            new_cache.update(c)
    elif spec.mixer == "rglru":
        mix, c = rec.rglru_block(cfg, p, h, cache=cache,
                                 return_cache=return_cache)
        if c:
            new_cache.update(c)
    elif spec.mixer == "mlstm":
        mix, c = rec.mlstm_block(cfg, p, h, cache=cache,
                                 return_cache=return_cache)
        if c:
            new_cache.update(c)
    elif spec.mixer == "slstm":
        mix, c = rec.slstm_block(cfg, p, h, cache=cache,
                                 return_cache=return_cache)
        if c:
            new_cache.update(c)
    else:
        raise ValueError(spec.mixer)

    if cfg.post_norms:
        mix = L.apply_norm(cfg, p, "norm1_post", mix)

    if cfg.parallel_block and spec.ffn != "none":
        # command-r style: ffn reads the same normed input, one residual add
        ff = (L.mlp_block(cfg, p, h) if spec.ffn == "mlp"
              else moe_mod.moe_block(cfg, p, h))
        ff = jax.ad_checkpoint.checkpoint_name(ff + mix, "block_out")
        x = x + ff
        return x, (new_cache or None)

    x = x + mix

    if spec.cross_attn:
        hx = L.apply_norm(cfg, p, "norm_x", x)
        if cache is not None and "xk" in cache:
            enc_kv = L.KVCache(cache["xk"], cache["xv"])
            # cross-KV is static during decode: carry it through unchanged
            new_cache.update({"xk": cache["xk"], "xv": cache["xv"]})
        else:
            enc_kv = L.encode_cross_kv(cfg, p, enc_out)
            if return_cache:
                new_cache.update({"xk": enc_kv.k, "xv": enc_kv.v})
        x = x + L.cross_attn_block(cfg, p, hx, enc_kv)

    if spec.ffn != "none":
        h2 = L.apply_norm(cfg, p, "norm2", x)
        ff = (L.mlp_block(cfg, p, h2) if spec.ffn == "mlp"
              else moe_mod.moe_block(cfg, p, h2))
        # saved under the remat policy: the backward pass re-derives the FFN
        # without re-executing its (EP/TP) psum (§Perf iteration 14)
        ff = jax.ad_checkpoint.checkpoint_name(ff, "block_out")
        if cfg.post_norms:
            ff = L.apply_norm(cfg, p, "norm2_post", ff)
        x = x + ff

    return x, (new_cache or None)


def _attn_with_cache(cfg, spec, p, h, *, positions, cache, write_pos,
                     return_cache, causal, factors=None, comp_len=None,
                     cache_slot=None):
    """attn_block + prefill cache construction + non-causal (encoder) path.
    With ``cache_slot`` the cache is the slot pool and the returned one the
    S new rows alone (see ``forward``)."""
    dt = h.dtype
    scale = cfg.query_scale or (1.0 / math.sqrt(cfg.head_dim))
    q, k, v = L.qkv_project(cfg, p, "attn", h)
    q = constrain(q, "batch", None, "model", None)
    k = constrain(k, "batch", None, "model", None)
    v = constrain(v, "batch", None, "model", None)
    if cfg.use_rope:
        cos, sin = L.rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)

    if cache is None:
        use_flash = (cfg.use_flash_kernel and causal and spec.window is None
                     and cfg.attn_softcap == 0.0)
        if use_flash:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=True, scale=scale)
        else:
            out = L.attention(q, k, v, causal=causal, window=spec.window,
                              scale=scale, cap=cfg.attn_softcap,
                              q_positions=positions, kv_positions=positions,
                              chunk=cfg.attn_chunk)
        kv = None
        if return_cache:
            if spec.window is not None and spec.window < k.shape[1]:
                kv = L.KVCache(k[:, -spec.window:], v[:, -spec.window:])
            else:
                kv = L.KVCache(k, v)
    else:
        # Write-then-attend: update the (possibly seq-sharded) cache in place
        # and attend over it with a causal mask.  Concatenating the new token
        # onto the sharded seq dim would force XLA to all-gather the whole
        # cache per layer (30 GB/token on qwen3 decode_32k — §Perf iter 13).
        s_kv = cache.k.shape[1]
        if spec.window is not None and s_kv <= spec.window:
            if q.shape[1] != 1 or cache_slot is not None:
                raise ValueError(f"a ring-buffer cache (window "
                                 f"{spec.window}, {s_kv} rows) takes one "
                                 f"token per call of its own batch, not "
                                 f"{q.shape[1]} or a pool slot")
            # ring buffer: slot i holds absolute position
            # write_pos - ((wp - i) mod s_kv)
            wp = jnp.mod(write_pos, s_kv)
            kv_pos = write_pos - jnp.mod(wp - jnp.arange(s_kv), s_kv)
        else:
            wp = write_pos
            kv_pos = jnp.arange(s_kv)
        if cache_slot is None:
            kv = L.KVCache(
                jax.lax.dynamic_update_slice_in_dim(
                    cache.k, k.astype(cache.k.dtype), wp, axis=1),
                jax.lax.dynamic_update_slice_in_dim(
                    cache.v, v.astype(cache.v.dtype), wp, axis=1))
            ak, av = kv.k, kv.v
        else:
            # one slot of the pool: attend over its rows with the chunk
            # written in, and hand back the chunk's rows alone, so that no
            # whole slot is copied out of the pool or back into it
            def slot_rows(c, new):
                rows = jax.lax.dynamic_slice_in_dim(c, cache_slot, 1, axis=0)
                return jax.lax.dynamic_update_slice_in_dim(rows, new, wp,
                                                           axis=1)
            kv = L.KVCache(k.astype(cache.k.dtype), v.astype(cache.v.dtype))
            ak, av = slot_rows(cache.k, kv.k), slot_rows(cache.v, kv.v)
        if factors and comp_len is not None and q.shape[1] == 1:
            # compressed-prefix decode (DESIGN.md §12): rows [0, comp_len_b)
            # of this cache live only as rank-r factors; the dense rows
            # there are zeroed, so attention must score the prefix through
            # the factors and the tail through the cache, in one softmax.
            # Only full-context layers carry factors (cache.build_kv_factors
            # eligibility), so the window mask never binds here.
            if cfg.use_flash_kernel:
                # fused Pallas kernel (kernels/factored_decode.py); the jnp
                # path below is its reference oracle (DESIGN.md §16)
                from repro.kernels import ops as kops
                out = kops.factored_decode_attention(
                    q, ak, av, factors["k_us"], factors["k_vt"],
                    factors["v_us"], factors["v_vt"], comp_len, write_pos,
                    scale=scale, cap=cfg.attn_softcap)
            else:
                out = L.factored_decode_attention(
                    q, ak, av, factors["k_us"], factors["k_vt"],
                    factors["v_us"], factors["v_vt"], comp_len,
                    write_pos=write_pos, scale=scale, cap=cfg.attn_softcap)
        else:
            out = L.attention(q, ak.astype(dt), av.astype(dt),
                              causal=causal, window=spec.window, scale=scale,
                              cap=cfg.attn_softcap,
                              q_positions=positions.reshape(-1),
                              kv_positions=kv_pos, chunk=cfg.attn_chunk)

    b, sq = out.shape[:2]
    out = out.reshape(b, sq, -1)
    out = jnp.dot(out, p["attn/wo"].astype(dt))
    return out, kv


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def apply_stack(cfg: ModelCfg, params: dict, x: jax.Array, *, positions,
                cache, write_pos, enc_out, return_cache: bool,
                causal: bool = True, pattern=None, prefix="layers",
                n_periods=None, n_rem=None, use_prelude: bool = True,
                kv_factors=None, comp_len=None, cache_slot=None):
    """Scanned pattern group + remainder layers."""
    pattern = pattern or cfg.pattern
    n_periods = cfg.n_scan_periods if n_periods is None else n_periods
    n_rem = cfg.n_remainder if n_rem is None else n_rem
    period = len(pattern)

    scan_p = sub(params, f"{prefix}/")
    has_cache = cache is not None
    scan_c = cache["scan"] if has_cache else None
    has_f = kv_factors is not None
    scan_f = kv_factors["scan"] if has_f else None

    # prelude layers (unrolled, before the scan group)
    new_pre = []
    prelude = cfg.prelude if use_prelude else ()
    for j, spec in enumerate(prelude):
        cj = cache["pre"][j] if has_cache else None
        fj = kv_factors["pre"][j] if has_f else None
        x, nc = apply_layer(cfg, spec, sub(params, f"pre{j}/"), x,
                            positions=positions, cache=cj,
                            write_pos=write_pos, enc_out=enc_out,
                            return_cache=return_cache, causal=causal,
                            factors=fj, comp_len=comp_len,
                            cache_slot=cache_slot)
        new_pre.append(nc if nc is not None else {})

    def period_body(x, p_i, c_i, f_i=None):
        new_cs = []
        for i, spec in enumerate(pattern):
            ci = c_i[i] if c_i is not None else None
            fi = f_i[i] if f_i is not None else None
            x, nc = apply_layer(cfg, spec, sub(p_i, f"p{i}/"), x,
                                positions=positions, cache=ci,
                                write_pos=write_pos, enc_out=enc_out,
                                return_cache=return_cache, causal=causal,
                                factors=fi, comp_len=comp_len,
                                cache_slot=cache_slot)
            new_cs.append(nc if nc is not None else {})
        return x, tuple(new_cs)

    training = not has_cache and not return_cache
    if cfg.remat and training:
        # full remat (save nothing): a save_only_these_names("block_out")
        # policy was measured byte-identical on collectives (§Perf iter 14,
        # refuted) so the memory-lean default stays
        period_body = jax.checkpoint(period_body)

    new_scan = None
    if n_periods and cfg.unroll_scans:
        # cost-probe mode: python loop so every period's FLOPs are lowered
        idx = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
        new_cs = []
        for i in range(n_periods):
            x, nc = period_body(x, idx(scan_p, i),
                                idx(scan_c, i) if has_cache else None,
                                idx(scan_f, i) if has_f else None)
            new_cs.append(nc)
        if has_cache or return_cache:
            new_scan = jax.tree.map(lambda *xs: jnp.stack(xs), *new_cs)
    elif n_periods:
        if has_cache:
            def body(x, xs):
                p_i, c_i, f_i = xs
                return period_body(x, p_i, c_i, f_i)
            x, new_scan = jax.lax.scan(body, x, (scan_p, scan_c, scan_f))
        elif return_cache:  # prefill: collect stacked output caches
            def body2(x, p_i):
                return period_body(x, p_i, None)
            x, new_scan = jax.lax.scan(body2, x, scan_p)
        else:               # train: no cache in or out
            def body3(x, p_i):
                y, _ = period_body(x, p_i, None)
                return y, None
            x, _ = jax.lax.scan(body3, x, scan_p)

    new_rem = []
    for j in range(n_rem):
        spec = pattern[j % period]
        cj = cache["rem"][j] if has_cache else None
        fj = kv_factors["rem"][j] if has_f else None
        x, nc = apply_layer(cfg, spec, sub(params, f"rem{j}/"), x,
                            positions=positions, cache=cj,
                            write_pos=write_pos, enc_out=enc_out,
                            return_cache=return_cache, causal=causal,
                            factors=fj, comp_len=comp_len,
                            cache_slot=cache_slot)
        new_rem.append(nc if nc is not None else {})

    new_cache = None
    if has_cache or return_cache:
        new_cache = {"pre": tuple(new_pre), "scan": new_scan,
                     "rem": tuple(new_rem)}
    return x, new_cache


# ---------------------------------------------------------------------------
# Full model forward
# ---------------------------------------------------------------------------

class ForwardOut(NamedTuple):
    logits: jax.Array
    cache: Optional[dict]


def _batch_axes(mesh, batch_dim: Optional[int] = None):
    from repro.sharding import activation as A
    ba = A._resolve(mesh, "batch")
    if ba is None or batch_dim is None:
        return ba
    size = 1
    for ax in (ba if isinstance(ba, tuple) else (ba,)):
        size *= mesh.shape[ax]
    return ba if batch_dim % size == 0 else None  # long_500k: batch=1


def embed_tokens(cfg, params, tokens):
    """Vocab-parallel lookup (shard_map): each vocab shard gathers its own
    rows and a (B,S,D) psum over `model` combines — no replicating gather
    (the XLA fallback that caused 'involuntary full rematerialization' in
    the dry-run) and no materialized one-hot (EXPERIMENTS.md §Perf iter 1/3)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.activation import get_mesh
    table = params["embed/tokens"]
    mesh = get_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and table.shape[0] % mesh.shape["model"] == 0:
        ba = _batch_axes(mesh, tokens.shape[0])
        act_dt = _act_dtype(cfg)

        def lookup(tok, tbl):  # tbl: (V/model, D) local shard
            vloc = tbl.shape[0]
            lo = jax.lax.axis_index("model") * vloc
            local = jnp.clip(tok - lo, 0, vloc - 1)
            vals = tbl[local].astype(act_dt)
            mask = ((tok >= lo) & (tok < lo + vloc))[..., None]
            return jax.lax.psum(jnp.where(mask, vals, 0), "model")

        x = jax.shard_map(lookup, mesh=mesh,
                       in_specs=(P(ba, None), P("model", None)),
                       out_specs=P(ba, None, None),
                       check_vma=False)(tokens, table)
    else:
        x = table[tokens].astype(_act_dtype(cfg))
    x = constrain(x, "batch", None, None)
    if cfg.embed_scale:
        x = x * math.sqrt(cfg.d_model)
    return x


def encoder_forward(cfg, params, enc_embeds):
    """Whisper encoder: stub frame embeddings -> bidirectional stack."""
    dt = _act_dtype(cfg)
    x = enc_embeds.astype(dt)
    pos = L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(dt)
    x = x + pos[None]
    enc_cfg = cfg.with_(use_rope=False)
    enc_pattern = (LayerSpec(mixer="attn", ffn="mlp"),)
    x, _ = apply_stack(enc_cfg, sub(params, "enc/"), x,
                       positions=jnp.arange(x.shape[1]), cache=None,
                       write_pos=0, enc_out=None, return_cache=False,
                       causal=False, pattern=enc_pattern, prefix="layers",
                       n_periods=cfg.encdec.enc_layers, n_rem=0,
                       use_prelude=False)
    return L.apply_norm(cfg, sub(params, "enc/"), "final_norm", x)


def forward(cfg: ModelCfg, params: dict, tokens: jax.Array, *,
            cache: Optional[dict] = None, write_pos=0,
            img_embeds: Optional[jax.Array] = None,
            enc_embeds: Optional[jax.Array] = None,
            return_cache: bool = False,
            kv_factors: Optional[dict] = None,
            comp_len: Optional[jax.Array] = None,
            cache_slot: Optional[jax.Array] = None) -> ForwardOut:
    """tokens: (B, S).  Decode: S == 1 with a populated cache; a cached
    call of S > 1 tokens (single-slot chunked prefill) sits at positions
    ``write_pos + arange(S)`` and attends causally over the cache.

    ``cache_slot`` (serving prefill; attention over plain rows only): the
    cache is the whole slot pool, ``tokens`` (1, S) belong to its slot
    ``cache_slot``, attention reads that slot's rows alone, and the
    returned cache holds each layer's S new rows, (1, S, ...) in place of
    the pool, for the caller to write at ``(cache_slot, write_pos)``.

    ``kv_factors``/``comp_len`` (serving only, DESIGN.md §12): a
    ``cache.build_kv_factors`` pytree of per-layer rank-r KV factors plus the
    per-batch-row compressed-prefix length; decode attention for eligible
    layers scores rows [0, comp_len_b) through the factors (the dense cache
    rows there are zeroed by the engine) and the tail through the cache."""
    dt = _act_dtype(cfg)
    x = embed_tokens(cfg, params, tokens)

    if cfg.vlm is not None and img_embeds is not None:
        img = jnp.dot(img_embeds.astype(dt), params["vlm/proj"].astype(dt))
        x = jnp.concatenate([img, x], axis=1)

    if cache is not None:
        # a cached call of S tokens writes and attends at write_pos + i
        positions = jnp.asarray(write_pos) + jnp.arange(x.shape[1])
    else:
        positions = jnp.arange(x.shape[1])

    enc_out = None
    if cfg.encdec is not None:
        if enc_embeds is not None:
            enc_out = encoder_forward(cfg, params, enc_embeds)
        # whisper decoder positions are sinusoidal at the absolute positions
        pe = L.sinusoidal_at(positions, cfg.d_model).astype(dt)
        x = x + pe[None]

    x, new_cache = apply_stack(cfg, params, x, positions=positions,
                               cache=cache, write_pos=write_pos,
                               enc_out=enc_out, return_cache=return_cache,
                               kv_factors=kv_factors, comp_len=comp_len,
                               cache_slot=cache_slot)

    x = L.apply_norm(cfg, params, "final_norm", x)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed/tokens"].astype(dt))
    else:
        logits = jnp.dot(x, params["unembed"].astype(dt))
    logits = constrain(logits, "batch", None, "vocab")  # vocab stays sharded
    # logits STAY bf16 here: the f32 upcast (+ final softcap) happens inside
    # the loss / sampling consumers, so the backward cotangent through the
    # unembedding and the whole residual stream is bf16, halving every
    # backward TP psum (§Perf iteration 11)
    return ForwardOut(logits, new_cache)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array,
                  final_softcap: float = 0.0) -> jax.Array:
    """Masked CE; labels < 0 are ignored (VLM image positions, padding).

    Vocab-parallel form (shard_map, Megatron-style): each vocab shard
    computes its local max / exp-sum / masked gold gather; only (B,S)
    statistics cross the wire.  Avoids both the full-logits all-reduce
    (take_along_axis on a sharded dim) and any materialized one-hot
    (EXPERIMENTS.md §Perf iterations 1 & 3).  Logits arrive bf16 and are
    upcast (+ softcapped) LOCALLY so the cotangent leaving here is bf16
    (§Perf iteration 11).
    """
    from jax.sharding import PartitionSpec as P
    from repro.sharding.activation import get_mesh
    mask = labels >= 0
    safe = jnp.maximum(labels, 0)
    mesh = get_mesh()
    if mesh is not None and "model" in mesh.axis_names \
            and logits.shape[-1] % mesh.shape["model"] == 0:
        ba = _batch_axes(mesh, logits.shape[0])

        def vp_nll(lg, lb):  # lg: (B,S,V/model) local; lb: (B,S)
            lg = L.softcap(lg.astype(jnp.float32), final_softcap)
            vloc = lg.shape[-1]
            lo = jax.lax.axis_index("model") * vloc
            # per-shard logsumexp (locally max-stabilized), then a tiny
            # (n_shards, B, S) all_gather — differentiable end to end
            lse_loc = jax.nn.logsumexp(lg, axis=-1)
            logz = jax.nn.logsumexp(
                jax.lax.all_gather(lse_loc, "model"), axis=0)
            local = jnp.clip(lb - lo, 0, vloc - 1)
            g = jnp.take_along_axis(lg, local[..., None], axis=-1)[..., 0]
            owned = (lb >= lo) & (lb < lo + vloc)
            gold = jax.lax.psum(jnp.where(owned, g, 0.0), "model")
            return logz - gold

        nll = jax.shard_map(vp_nll, mesh=mesh,
                         in_specs=(P(ba, None, "model"), P(ba, None)),
                         out_specs=P(ba, None),
                         check_vma=False)(logits, safe)
    else:
        lg = L.softcap(logits.astype(jnp.float32), final_softcap)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
        nll = logz - gold
    nll = nll * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def cast_params_for_compute(cfg: ModelCfg, params: dict) -> dict:
    """Cast f32 masters to the activation dtype ONCE, ahead of the layer
    scan, so FSDP all-gathers and HBM reads move bf16 (half the bytes) —
    grads flow back to the f32 masters through the cast (§Perf iteration 2).
    Norm scales stay f32 (cheap, accuracy-sensitive).

    Auto-layout non-TP mode (§Perf iteration 3): weights are additionally
    constrained to REPLICATED here — true ZeRO semantics (gather the weights,
    not the activations; observed XLA otherwise gathers the 3072-wide mlp
    hidden per layer).  The vocab-sharded embedding/unembedding tables are
    excluded: logits must stay vocab-parallel."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding.activation import (get_mesh, get_tp, pin_param,
                                           replicate)
    dt = _act_dtype(cfg)
    mesh = get_mesh()
    unshard = mesh is not None and not get_tp()

    def cast(k, w):
        if w.dtype == jnp.float32 and w.ndim >= 2:
            # pin the bf16 copy to the source sharding so the downstream
            # gather moves bf16, not f32 (§Perf iteration 10)
            w = pin_param(k, w.astype(dt))
        if mesh is None or w.ndim < 2:
            return w
        # expert weights: pre-layout to exactly the shard_map in_specs
        # (experts -> model, D gathered over data) ONCE per step, in bf16 —
        # otherwise every scan iteration re-gathers them in f32
        # (§Perf iteration 8)
        if "/moe/w_" in k and "shared" not in k:
            if "model" in mesh.axis_names and \
                    w.shape[-3 if w.ndim >= 3 else 0] % mesh.shape["model"] == 0:
                lead = (None,) * (w.ndim - 3)
                w = jax.lax.with_sharding_constraint(
                    w, NamedSharding(mesh, P(*lead, "model", None, None)))
            return w
        if unshard and k not in ("embed/tokens", "unembed"):
            w = replicate(w)
        return w

    return {k: cast(k, w) for k, w in params.items()}


def loss_fn(cfg: ModelCfg, params: dict, batch: dict) -> jax.Array:
    out = forward(cfg, params, batch["tokens"],
                  img_embeds=batch.get("img_embeds"),
                  enc_embeds=batch.get("enc_embeds"))
    logits = out.logits
    labels = batch["labels"]
    if cfg.vlm is not None:
        # image positions prepended: mask them out of the loss
        n_img = cfg.vlm.num_image_tokens
        pad = jnp.full(labels.shape[:1] + (n_img,), -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    return cross_entropy(logits, labels, cfg.final_softcap)
