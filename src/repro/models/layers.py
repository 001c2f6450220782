"""Shared neural building blocks (pure functions over param pytrees).

Conventions:
  * params are plain dicts of jnp arrays; every ``init_*`` has a matching
    apply function;
  * activations keep ``cfg.dtype`` (bf16); norms/softmax accumulate in f32;
  * attention is grouped-query: H query heads share KH kv heads (G = H/KH);
  * all sequence-mixing functions are shape-polymorphic over batch/sequence
    so the same code serves train, prefill and decode.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig

Params = Dict[str, jnp.ndarray]


# -- basics -------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) \
        * (d_in ** -0.5)
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def init_norm(d: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm(p: Params, x, kind: str = "rmsnorm", eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32)).astype(x.dtype)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotary embedding. x: (..., S, D) with positions (..., S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return rotated.astype(x.dtype)


def activation(x, kind: str):
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


# -- MLP -----------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None,
             bias: bool = False) -> Params:
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"up": init_linear(ks[0], cfg.d_model, f, dtype, bias),
         "down": init_linear(ks[1], f, cfg.d_model, dtype, bias)}
    if cfg.gated_mlp:
        p["gate"] = init_linear(ks[2], cfg.d_model, f, dtype, bias)
    return p


def mlp(p: Params, x, cfg: ModelConfig):
    h = linear(p["up"], x)
    if "gate" in p:
        h = h * activation(linear(p["gate"], x), cfg.act)
    else:
        h = activation(h, cfg.act)
    return linear(p["down"], h)


# -- attention -------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype, cross: bool = False,
                   bias: bool = False) -> Params:
    d, h, kh, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {"wq": init_linear(ks[0], d, h * dh, dtype, bias),
         "wk": init_linear(ks[1], d, kh * dh, dtype, bias),
         "wv": init_linear(ks[2], d, kh * dh, dtype, bias),
         "wo": init_linear(ks[3], h * dh, d, dtype, bias)}
    if cfg.qk_norm:
        p["q_norm"] = init_norm(dh, "rmsnorm", dtype)
        p["k_norm"] = init_norm(dh, "rmsnorm", dtype)
    return p


def _attend(q, k, v, mask):
    """Grouped-query core. q: (B,S,KH,G,D); k,v: (B,T,KH,D); mask: (B,S,T) bool."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out


def _attend_cached(q, ck, cv, k, v, here, mask):
    """Decode core over a cache that is read and never written.

    q: (B,1,KH,G,D); ck, cv: (B,KH,T,D); k, v: (B,KH,1,D), the token's fresh
    row, which belongs at the cache position ``here`` (T,) marks; mask:
    (B,1,T) bool, that position included. The fresh row's score takes that
    position's place, so the softmax runs over the same scores in the same
    order as ``_attend`` over the cache with the row written in.
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bskgd,bktd->bkgst", q, ck).astype(jnp.float32) * scale
    fresh = jnp.einsum("bskgd,bktd->bkgst", q, k).astype(jnp.float32) * scale
    scores = jnp.where(here, fresh, scores)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    p_fresh = jnp.sum(jnp.where(here, probs, 0), axis=-1, keepdims=True)
    out = jnp.einsum("bkgst,bktd->bskgd", jnp.where(here, 0, probs), cv,
                     preferred_element_type=jnp.float32) \
        + jnp.einsum("bkgst,bktd->bskgd", p_fresh, v,
                     preferred_element_type=jnp.float32)
    return out.astype(v.dtype)


def _flash(q, k, v, *, causal, window, interpret):
    from ..kernels import ops as kops
    B, S, KH, G, D = q.shape
    qf = q.reshape(B, S, KH * G, D).transpose(0, 2, 1, 3)     # (B,H,S,D)
    kf = k.transpose(0, 2, 1, 3)                              # (B,KH,T,D)
    vf = v.transpose(0, 2, 1, 3)
    out = kops.flash_attention(qf, kf, vf, causal=causal, window=window,
                               interpret=interpret)
    return out.transpose(0, 2, 1, 3).reshape(B, S, KH, G, D)


def cross_kv(p: Params, cfg: ModelConfig, enc_out):
    """Precompute cross-attention K/V from encoder states: (B,T,KH,D) each."""
    B, T, _ = enc_out.shape
    k = linear(p["wk"], enc_out).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    v = linear(p["wv"], enc_out).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
    return k, v


CHUNKED_THRESHOLD = 1 << 21    # S*T above this -> memory-efficient attention


def cross_attention(p: Params, x, cfg: ModelConfig, kv):
    """Decoder cross-attention over precomputed encoder K/V (no mask)."""
    B, S, _ = x.shape
    kh, g, dh = cfg.n_kv_heads, cfg.kv_groups, cfg.d_head
    q = linear(p["wq"], x).reshape(B, S, kh, g, dh)
    k, v = kv
    if S * k.shape[1] >= CHUNKED_THRESHOLD:
        from ..kernels.ref import chunked_attention
        out = chunked_attention(q, k, v, False, None)
    else:
        mask = jnp.ones((B, S, k.shape[1]), bool)
        out = _attend(q, k, v, mask)
    out = out.reshape(B, S, cfg.n_heads * dh)
    return linear(p["wo"], out.astype(x.dtype))


def make_causal_mask(positions_q, positions_k, window=None):
    """(B,S),(B,T) -> (B,S,T) bool. ``window`` (static or traced) limits
    lookback for local attention; None = unbounded."""
    m = positions_q[:, :, None] >= positions_k[:, None, :]
    if window is not None:
        m &= (positions_q[:, :, None] - positions_k[:, None, :]) < window
    return m


def attention(p: Params, x, cfg: ModelConfig, *, positions, kv_x=None,
              mask=None, causal=True, window=None, use_rope=True,
              cache: Optional[Tuple] = None, cache_pos=None,
              cache_length=None):
    """Self/cross attention with optional KV cache.

    window: None = unbounded; a *static int* enables the Pallas flash path;
    in the decode path it may also be a traced scalar (gemma3's per-layer
    local/global interleave rides through one scan).

    Decode (one token): cache is (k_cache, v_cache), each head-major
    (B, KH, S_max, D), so that a head's rows lie together as the dot reads
    them, and cache_pos the position the token's row belongs at. The cache
    is read, never written: the token attends over the cached rows in
    [start, cache_pos) together with its own fresh k/v, cast to the cache
    dtype. The caller writes that row at cache_pos after its layer loop.
    cache_length overrides the #valid slots (ring caches write at pos %% W
    but stay fully valid once warm).

    Returns (out, (k, v)): the k/v this call computed, (B, S, KH, D); in
    decode the one new row, (B, KH, 1, D) in the cache dtype.
    """
    B, S, _ = x.shape
    h, kh, dh, g = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.kv_groups
    q = linear(p["wq"], x).reshape(B, S, kh, g, dh)
    src = x if kv_x is None else kv_x
    k = linear(p["wk"], src).reshape(B, src.shape[1], kh, dh)
    v = linear(p["wv"], src).reshape(B, src.shape[1], kh, dh)
    if cfg.qk_norm:
        q = norm(p["q_norm"], q)
        k = norm(p["k_norm"], k)
    if use_rope and kv_x is None:
        q = rope(q.reshape(B, S, kh * g, dh).transpose(0, 2, 1, 3),
                 positions[:, None, :], cfg.rope_theta) \
            .transpose(0, 2, 1, 3).reshape(B, S, kh, g, dh)
        k = rope(k.transpose(0, 2, 1, 3), positions[:, None, :],
                 cfg.rope_theta).transpose(0, 2, 1, 3)

    if cache is not None and cache_pos is not None:
        ck, cv = cache
        k = k.transpose(0, 2, 1, 3).astype(ck.dtype)
        v = v.transpose(0, 2, 1, 3).astype(cv.dtype)
        T = ck.shape[2]
        length = cache_pos + 1 if cache_length is None else cache_length
        start = jnp.int32(0) if window is None \
            else jnp.maximum(jnp.int32(0), length - window)
        if cfg.attn_impl.startswith("pallas") and S == 1:
            # the kernel reads the new row from the cache: write it into a
            # copy of this layer's slice
            from ..kernels import ops as kops
            ck = jax.lax.dynamic_update_slice(ck, k, (0, 0, cache_pos, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, 0, cache_pos, 0))
            qd = q.reshape(B, kh * g, dh)
            out = kops.decode_attention(
                qd, ck, cv, length, start=start,
                interpret=cfg.attn_impl == "pallas_interpret")
            out = out.reshape(B, S, kh, g, dh)
        else:
            kpos = jnp.arange(T)
            m = (kpos < length) & (kpos >= start)
            m = jnp.broadcast_to(m[None, None, :], (B, S, T))
            out = _attend_cached(q, ck, cv, k, v, kpos == cache_pos, m)
        new_cache = (k, v)
    else:
        T = src.shape[1]
        use_flash = (cfg.attn_impl.startswith("pallas") and kv_x is None
                     and causal and mask is None
                     and (window is None or isinstance(window, int)))
        if use_flash:
            out = _flash(q, k, v, causal=True, window=window or 0,
                         interpret=cfg.attn_impl == "pallas_interpret")
        elif mask is None and S * T >= CHUNKED_THRESHOLD:
            # memory-efficient O(S) attention (flash-style double scan);
            # window may be a traced per-layer scalar (gemma3)
            from ..kernels.ref import chunked_attention
            out = chunked_attention(q, k, v, causal, window)
        else:
            if mask is None:
                pos_k = positions if kv_x is None \
                    else jnp.broadcast_to(jnp.arange(T)[None], (B, T))
                if causal:
                    mask = make_causal_mask(positions, pos_k, window)
                else:
                    mask = jnp.ones((B, S, T), bool)
            out = _attend(q, k, v, mask)
        new_cache = (k, v)

    out = out.reshape(B, S, h * dh).astype(x.dtype)
    return linear(p["wo"], out), new_cache
