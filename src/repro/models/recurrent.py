"""Recurrent mixers: RG-LRU (Griffin/recurrentgemma), mLSTM and sLSTM (xLSTM).

Numerics notes (documented deviations, DESIGN.md §8):
  * mLSTM uses sigmoid input/forget gates instead of the stabilized
    exponential gating of the xLSTM paper — identical state-update structure,
    FLOPs and state shapes, but no stabilizer bookkeeping.  Computed in the
    chunked parallel form (intra-chunk quadratic + inter-chunk recurrent
    state), so train/prefill cost is O(S * chunk) not O(S^2).
  * RG-LRU follows Griffin: a_t = exp(-c * softplus(lambda) * sigmoid(r_t)),
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) (i_t * x_t), computed with an
    associative scan (O(log S) depth) for train and a single fused step for
    decode.
  * sLSTM keeps the per-head block-diagonal recurrence R, scanned over time.

All recurrent state caches are O(1) in sequence length — these mixers carry
the long_500k shape (DESIGN.md §5).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.layers import activation

_RG_C = 8.0  # Griffin's fixed recurrence sharpness


# ---------------------------------------------------------------------------
# Depthwise causal conv1d (width W) — shift-and-add form, decode-friendly
# ---------------------------------------------------------------------------

def causal_conv1d(x, w, conv_state=None):
    """x: (B,S,C); w: (W,C) depthwise.  conv_state: (B,W-1,C) previous inputs
    (decode).  Returns (y, new_state); a given state keeps its dtype."""
    width = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)          # (B, S+W-1, C)
    y = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(width))
    new_state = xp[:, -(width - 1):]
    if conv_state is not None:
        new_state = new_state.astype(conv_state.dtype)
    return y, new_state


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

def _rglru_scan(a, b, h0):
    """h_t = a_t * h_{t-1} + b_t via associative scan; a,b: (B,S,C) f32."""
    if h0 is not None:
        # fold the initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def rglru_block(cfg, p, x, *, cache, return_cache: bool):
    """Griffin recurrent block: lin_in -> conv -> RG-LRU -> gate -> lin_out."""
    dt = x.dtype
    dr = cfg.rnn.d_rnn or cfg.d_model
    u = jnp.dot(x, p["rnn/w_in"].astype(dt))        # (B,S,Dr)
    gate = jnp.dot(x, p["rnn/w_gate_in"].astype(dt))
    conv_state = cache.get("conv") if cache is not None else None
    u, new_conv = causal_conv1d(u, p["rnn/conv_w"].astype(dt),
                                conv_state)

    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(jnp.dot(uf, p["rnn/w_a"].astype(jnp.float32)))
    i = jax.nn.sigmoid(jnp.dot(uf, p["rnn/w_x"].astype(jnp.float32)))
    log_a = -_RG_C * jax.nn.softplus(
        p["rnn/lam"].astype(jnp.float32)) * r       # (B,S,Dr)
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * uf)

    h0 = cache.get("h") if cache is not None else None
    if x.shape[1] == 1 and cache is not None:
        h = a[:, 0] * h0 + b[:, 0]
        hs = h[:, None]
        new_h = h
    else:
        hs = _rglru_scan(a, b, h0)
        new_h = hs[:, -1]

    out = hs.astype(dt) * activation("gelu", gate)
    out = jnp.dot(out, p["rnn/w_out"].astype(dt))
    new_cache = ({"h": new_h, "conv": new_conv}
                 if (return_cache or cache is not None) else None)
    return out, new_cache


# ---------------------------------------------------------------------------
# mLSTM (chunked matrix-memory linear attention)
# ---------------------------------------------------------------------------

def _mlstm_chunk(q, k, v, li, lf_c, state):
    """One chunk.  q,k,v: (B,H,T,hd); li: (B,H,T) log input gate;
    lf_c: (B,H,T) cumulative log forget within chunk (inclusive).
    state: (C (B,H,hd,hd), n (B,H,hd)).  Returns (h, new_state)."""
    c_prev, n_prev = state
    t = q.shape[2]
    # intra-chunk decay: w_ij = exp(lf_i - lf_j + li_j), j <= i  (all <= 0 in
    # the exponent up to li, sigmoid-gated => stable)
    d = lf_c[:, :, :, None] - lf_c[:, :, None, :] + li[:, :, None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))
    w = jnp.where(mask[None, None], jnp.exp(d), 0.0)
    scores = jnp.einsum("bhid,bhjd->bhij", q, k) * w
    num_intra = jnp.einsum("bhij,bhjd->bhid", scores, v)
    den_intra = jnp.einsum("bhij,bhjd->bhid", w, k)
    # inter-chunk: decay from chunk start
    decay = jnp.exp(lf_c)[..., None]                      # (B,H,T,1)
    num_inter = jnp.einsum("bhid,bhde->bhie", q, c_prev) * decay
    den_inter = n_prev[:, :, None, :] * decay
    num = num_intra + num_inter
    den = jnp.einsum("bhid,bhid->bhi",
                     q, den_intra + den_inter)
    h = num / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    # state to chunk end: decay exp(lf_T - lf_j + li_j)
    wT = jnp.exp(lf_c[:, :, -1:, ] - lf_c + li)           # (B,H,T)
    c_new = jnp.exp(lf_c[:, :, -1])[..., None, None] * c_prev + jnp.einsum(
        "bhj,bhjd,bhje->bhde", wT, k, v)
    n_new = jnp.exp(lf_c[:, :, -1])[..., None] * n_prev + jnp.einsum(
        "bhj,bhjd->bhd", wT, k)
    return h, (c_new, n_new)


def mlstm_block(cfg, p, x, *, cache, return_cache: bool,
                chunk: int = 256):
    """xLSTM mLSTM block: up-proj (factor 2) -> conv -> q/k/v + gates ->
    chunked matrix-memory attention -> gated down-proj."""
    dt = x.dtype
    b, s, d = x.shape
    di = int(cfg.rnn.mlstm_proj_factor * d)
    nh = cfg.n_heads
    hd = di // nh

    u = jnp.dot(x, p["mlstm/w_up"].astype(dt))      # (B,S,Di)
    z = jnp.dot(x, p["mlstm/w_z"].astype(dt))       # gate branch
    conv_state = cache.get("conv") if cache is not None else None
    uc, new_conv = causal_conv1d(u, p["mlstm/conv_w"].astype(dt),
                                 conv_state)
    uc = activation("silu", uc)

    def heads(t):
        return t.reshape(b, s, nh, hd).transpose(0, 2, 1, 3)  # (B,H,S,hd)

    q = heads(jnp.dot(uc, p["mlstm/wq"].astype(dt))).astype(jnp.float32)
    k = heads(jnp.dot(uc, p["mlstm/wk"].astype(dt))).astype(jnp.float32)
    v = heads(jnp.dot(u, p["mlstm/wv"].astype(dt))).astype(jnp.float32)
    q = q / math.sqrt(hd)

    gi = jnp.einsum("bsi,ih->bsh", u.astype(jnp.float32),
                    p["mlstm/w_ig"].astype(jnp.float32))
    gf = jnp.einsum("bsi,ih->bsh", u.astype(jnp.float32),
                    p["mlstm/w_fg"].astype(jnp.float32))
    li = jax.nn.log_sigmoid(gi).transpose(0, 2, 1)            # (B,H,S)
    lf = jax.nn.log_sigmoid(gf).transpose(0, 2, 1)

    if cache is not None:
        c0 = cache["c"].astype(jnp.float32)
        n0 = cache["n"].astype(jnp.float32)
    else:
        c0 = jnp.zeros((b, nh, hd, hd), jnp.float32)
        n0 = jnp.zeros((b, nh, hd), jnp.float32)

    cs = min(chunk, s)
    if s % cs:
        cs = s
    nchunks = s // cs

    if nchunks == 1:
        h, (c_new, n_new) = _mlstm_chunk(q, k, v, li, jnp.cumsum(lf, -1),
                                         (c0, n0))
    else:
        def split(t):  # (B,H,S,hd) -> (nchunks, B, H, cs, hd)
            return jnp.moveaxis(t.reshape(b, nh, nchunks, cs, hd), 2, 0)

        qs, ks, vs = split(q), split(k), split(v)
        lis = jnp.moveaxis(li.reshape(b, nh, nchunks, cs), 2, 0)
        lfs = jnp.moveaxis(lf.reshape(b, nh, nchunks, cs), 2, 0)

        def body(state, xs):
            qc, kc, vc, lic, lfc = xs
            h, state = _mlstm_chunk(qc, kc, vc, lic, jnp.cumsum(lfc, -1), state)
            return state, h

        if getattr(cfg, "unroll_scans", False):
            # cost-probe mode: keep the chunked algorithm (same FLOPs as the
            # scanned version) but python-unroll so every chunk is lowered
            state = (c0, n0)
            hs_list = []
            for ci in range(nchunks):
                state, hc = body(state, (qs[ci], ks[ci], vs[ci], lis[ci],
                                         lfs[ci]))
                hs_list.append(hc)
            (c_new, n_new), hs = state, jnp.stack(hs_list)
        else:
            (c_new, n_new), hs = jax.lax.scan(body, (c0, n0),
                                              (qs, ks, vs, lis, lfs))
        h = jnp.moveaxis(hs, 0, 2).reshape(b, nh, s, hd)

    out = h.transpose(0, 2, 1, 3).reshape(b, s, di).astype(dt)
    out = out * activation("silu", z)
    out = jnp.dot(out, p["mlstm/w_down"].astype(dt))
    new_cache = ({"c": c_new, "n": n_new, "conv": new_conv}
                 if (return_cache or cache is not None) else None)
    return out, new_cache


# ---------------------------------------------------------------------------
# sLSTM (scalar memory, block-diagonal recurrence, time scan)
# ---------------------------------------------------------------------------

def slstm_block(cfg, p, x, *, cache, return_cache: bool):
    dt = x.dtype
    b, s, d = x.shape
    nh = cfg.n_heads
    hd = d // nh

    # input contributions for the 4 gates: (B,S,4D)
    wx = jnp.dot(x, p["slstm/w_x"].astype(dt)).astype(jnp.float32)
    r = p["slstm/r"].astype(jnp.float32)            # (H, hd, 4hd)

    if cache is not None:
        h0 = cache["h"].astype(jnp.float32)
        c0 = cache["c"].astype(jnp.float32)
        n0 = cache["n"].astype(jnp.float32)
    else:
        h0 = jnp.zeros((b, d), jnp.float32)
        c0 = jnp.zeros((b, d), jnp.float32)
        n0 = jnp.ones((b, d), jnp.float32)

    def step(carry, wx_t):
        h, c, n = carry
        hh = h.reshape(b, nh, hd)
        rec = jnp.einsum("bkh,khg->bkg", hh, r).reshape(b, 4 * d)
        g = wx_t + rec
        z, i, f, o = jnp.split(g, 4, axis=-1)
        z = jnp.tanh(z)
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        o = jax.nn.sigmoid(o)
        c = f * c + i * z
        n = f * n + i
        h = o * (c / jnp.maximum(n, 1e-6))
        return (h, c, n), h

    (h_f, c_f, n_f), hs = jax.lax.scan(step, (h0, c0, n0),
                                       jnp.moveaxis(wx, 1, 0))
    out = jnp.moveaxis(hs, 0, 1).astype(dt)                   # (B,S,D)
    out = jnp.dot(out, p["slstm/w_out"].astype(dt))
    new_cache = ({"h": h_f, "c": c_f, "n": n_f}
                 if (return_cache or cache is not None) else None)
    return out, new_cache
