"""Block programs for every assigned family, built on jax.lax.scan over
layers so compiled HLO size is O(1) in depth (essential: we compile 88-layer
models on one CPU host for the dry-run).

Three programs:
  * ``decoder``  — dense & MoE LMs, incl. gemma3's local:global interleave
                   (a per-layer traced window; params stay homogeneous);
  * ``hybrid``   — Jamba periods of [attention, (attn_period-1) x mamba] with
                   MoE FFN on alternating sublayers; scan over periods,
                   static unroll inside one period;
  * ``encdec``   — Whisper: bidirectional encoder + causal decoder with
                   cross-attention to cached encoder states.

Each program exposes init / forward (teacher-forced) / prefill / decode with
a uniform cache pytree, so model.py can treat all families identically.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .layers import (attention, cross_attention, cross_kv, init_attention,
                     init_mlp, init_norm, linear, make_causal_mask, mlp, norm)
from .moe import init_moe, moe
from .ssm import (init_mamba, mamba_decode, mamba_prefill, mamba_state_shapes)

Params = Dict[str, Any]
BIG_WINDOW = 2 ** 30   # plain int: no backend init at import time


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _stacked_init(key, n: int, init_fn):
    """vmap an init over a leading layer axis."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def sinusoid_positions(S: int, d: int, dtype):
    pos = np.arange(S)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10_000.0, 2 * dim / d)
    emb = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(emb, dtype)


# ===========================================================================
# decoder program (dense / moe / gemma3)
# ===========================================================================

def _layer_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer effective attention window (traced into the scan)."""
    if cfg.local_global_period:
        idx = np.arange(cfg.n_layers)
        is_global = (idx + 1) % cfg.local_global_period == 0
        return jnp.where(jnp.asarray(is_global), jnp.int32(BIG_WINDOW),
                         jnp.int32(cfg.window))
    w = cfg.window if cfg.window else int(BIG_WINDOW)
    return jnp.full((cfg.n_layers,), w, jnp.int32)


def init_decoder(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)

    def layer_init(k):
        kk = jax.random.split(k, 4)
        p = {"ln1": init_norm(cfg.d_model, cfg.norm, dtype),
             "attn": init_attention(kk[0], cfg, dtype),
             "ln2": init_norm(cfg.d_model, cfg.norm, dtype)}
        if cfg.family == "moe":
            p["ffn"] = init_moe(kk[1], cfg, dtype)
        else:
            p["ffn"] = init_mlp(kk[1], cfg, dtype)
        return p

    params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "layers": _stacked_init(ks[1], cfg.n_layers, layer_init),
        "final_norm": init_norm(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(
            ks[2], (cfg.d_model, cfg.vocab), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)
    return params


def _decoder_block(cfg: ModelConfig, lp: Params, x, positions, window,
                   cache_kv=None, cache_pos=None):
    """One pre-norm block. Returns (x, aux, kv).

    ``window`` is a traced per-layer scalar when local_global_period is set
    (gemma3); otherwise the static config window lets the flash path engage.
    """
    xn = norm(lp["ln1"], x, cfg.norm)
    if cache_kv is None:
        if cfg.local_global_period:
            # traced per-layer window rides through one homogeneous scan
            h, kv = attention(lp["attn"], xn, cfg, positions=positions,
                              window=window)
        else:
            h, kv = attention(lp["attn"], xn, cfg, positions=positions,
                              window=cfg.window or None)
    else:
        h, kv = attention(lp["attn"], xn, cfg, positions=positions,
                          cache=cache_kv, cache_pos=cache_pos, window=window)
    x = x + h
    hn = norm(lp["ln2"], x, cfg.norm)
    if cfg.family == "moe":
        f, aux = moe(lp["ffn"], hn, cfg)
    else:
        f, aux = mlp(lp["ffn"], hn, cfg), jnp.float32(0.0)
    return x + f, aux, kv


def decoder_forward(params: Params, cfg: ModelConfig, tokens,
                    want_cache: bool = False):
    """Teacher-forced forward. tokens: (B,S) int32 -> logits (B,S,V)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.family == "encdec":
        raise ValueError("use encdec_* for whisper")
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    windows = _layer_windows(cfg)

    def body(carry, xs):
        x, aux = carry
        lp, window = xs
        x, a, kv = _decoder_block(cfg, lp, x, positions, window)
        return (x, aux + a), (kv if want_cache else None)

    body = _remat(body, cfg)
    (x, aux), kvs = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                 (params["layers"], windows))
    x = norm(params["final_norm"], x, cfg.norm)
    head = params.get("head")
    logits = x @ (head if head is not None else params["embed"].T.astype(x.dtype))
    if cfg.logit_cap > 0:
        logits = cfg.logit_cap * jnp.tanh(logits / cfg.logit_cap)
    return logits, aux, kvs


def _head_major(kv):
    """(L,B,S,KH,D) k or v from a forward pass -> the caches' (L,B,KH,S,D)."""
    return kv.transpose(0, 1, 3, 2, 4)


def _pad_seq(kv, pad: int):
    """Pad an (L,B,KH,S,D) cache leaf with ``pad`` empty positions."""
    return jnp.pad(kv, ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))


def decoder_init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    dtype = jnp.dtype(cfg.dtype)
    kv = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, max_seq, cfg.d_head),
                   dtype)
    return {"k": kv, "v": kv, "pos": jnp.int32(0)}


def decoder_prefill(params: Params, cfg: ModelConfig, tokens, max_seq: int,
                    length=None):
    """Run the prompt, build the cache, return last-position logits.

    ``length`` (optional, traced scalar) marks the true prompt length when
    ``tokens`` is right-padded to a compile bucket: logits are gathered at
    ``length - 1`` and the cache write cursor starts at ``length``.  Causality
    makes this exact — positions >= length never influence the gathered
    logits, and the stale pad K/V rows sit at positions the decode mask
    excludes until they are overwritten by real decode steps.
    """
    B, S = tokens.shape
    logits, _, kvs = decoder_forward(params, cfg, tokens, want_cache=True)
    k, v = (_pad_seq(_head_major(kv), max_seq - S) for kv in kvs)
    if length is None:
        last, pos = logits[:, -1], jnp.int32(S)
    else:
        pos = jnp.asarray(length, jnp.int32)
        last = jnp.take(logits, pos - 1, axis=1)
    cache = {"k": k.astype(jnp.dtype(cfg.dtype)),
             "v": v.astype(jnp.dtype(cfg.dtype)), "pos": pos}
    return last, cache


def _layer(cache_leaf, layer):
    """One layer's slice of a stacked cache, read where it lies. The loop
    body indexes the cache rather than scanning it as ``xs``: a scan would
    hand each layer a copy, and under the serving engine's vmap move the
    slot axis behind the layer axis (a transpose of the whole cache)."""
    return jax.lax.dynamic_index_in_dim(cache_leaf, layer, 0, keepdims=False)


def _write_rows(cache_leaf, rows, pos):
    """Write the new (L,B,KH,1,D) rows at sequence position ``pos`` of an
    (L,B,KH,Smax,D) cache: a decode step's one write to it, in place when
    the cache is donated (a scatter under a vmap over per-slot ``pos``)."""
    return jax.lax.dynamic_update_slice(cache_leaf, rows, (0, 0, 0, pos, 0))


def decoder_decode(params: Params, cfg: ModelConfig, tokens, cache):
    """One decode step. tokens: (B,1); cache holds head-major
    (L,B,KH,Smax,D) K and V and the scalar write cursor ``pos``.

    The layer loop reads each layer's cache slice in place and never
    modifies it: attention takes the cached rows before ``pos`` together
    with the token's own fresh k/v. The loop returns only the new
    (L,B,KH,1,D) rows, and one write after it puts them at ``pos``.
    """
    B = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    windows = _layer_windows(cfg)

    def body(carry, xs):
        x, aux = carry
        lp, window, layer = xs
        kv = (_layer(cache["k"], layer), _layer(cache["v"], layer))
        x, a, rows = _decoder_block(cfg, lp, x, positions, window,
                                    cache_kv=kv, cache_pos=pos)
        return (x, aux + a), rows

    (x, _), (nks, nvs) = jax.lax.scan(
        body, (x, jnp.float32(0.0)),
        (params["layers"], windows, jnp.arange(cfg.n_layers)))
    x = norm(params["final_norm"], x, cfg.norm)
    head = params.get("head")
    logits = x @ (head if head is not None else params["embed"].T.astype(x.dtype))
    new_cache = {"k": _write_rows(cache["k"], nks, pos),
                 "v": _write_rows(cache["v"], nvs, pos), "pos": pos + 1}
    return logits[:, -1], new_cache


# ===========================================================================
# ssm program (mamba2 — attention-free stack)
# ===========================================================================

def init_ssm(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)

    def layer_init(k):
        return {"ln": init_norm(cfg.d_model, cfg.norm, dtype),
                "mamba": init_mamba(k, cfg, dtype)}

    params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "layers": _stacked_init(ks[1], cfg.n_layers, layer_init),
        "final_norm": init_norm(cfg.d_model, cfg.norm, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(
            ks[2], (cfg.d_model, cfg.vocab), jnp.float32)
            * cfg.d_model ** -0.5).astype(dtype)
    return params


def _ssm_logits(params, cfg, x):
    x = norm(params["final_norm"], x, cfg.norm)
    head = params.get("head")
    return x @ (head if head is not None else params["embed"].T.astype(x.dtype))


def ssm_forward(params: Params, cfg: ModelConfig, tokens,
                want_cache: bool = False):
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))

    def body(carry, lp):
        x = carry
        h, state = mamba_prefill(lp["mamba"], norm(lp["ln"], x, cfg.norm), cfg)
        return x + h, (state if want_cache else None)

    body = _remat(body, cfg)
    x, states = jax.lax.scan(body, x, params["layers"])
    return _ssm_logits(params, cfg, x), jnp.float32(0.0), states


def ssm_init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    dtype = jnp.dtype(cfg.dtype)
    conv_s, ssm_s = mamba_state_shapes(cfg, batch)
    return {"conv": jnp.zeros((cfg.n_layers,) + conv_s, dtype),
            "ssm": jnp.zeros((cfg.n_layers,) + ssm_s, dtype),
            "pos": jnp.int32(0)}


def ssm_prefill(params: Params, cfg: ModelConfig, tokens, max_seq: int):
    logits, _, states = ssm_forward(params, cfg, tokens, want_cache=True)
    conv, ssm_state = states
    cache = {"conv": conv, "ssm": ssm_state, "pos": jnp.int32(tokens.shape[1])}
    return logits[:, -1], cache


def ssm_decode(params: Params, cfg: ModelConfig, tokens, cache):
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))

    def body(carry, xs):
        x = carry
        lp, conv, ssm_state = xs
        h, (conv, ssm_state) = mamba_decode(
            lp["mamba"], norm(lp["ln"], x, cfg.norm), cfg, (conv, ssm_state))
        return x + h, (conv, ssm_state)

    x, (convs, ssms) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["ssm"]))
    logits = _ssm_logits(params, cfg, x)
    return logits[:, -1], {"conv": convs, "ssm": ssms, "pos": cache["pos"] + 1}


# ===========================================================================
# hybrid program (jamba: periods of [attn, mamba x (P-1)], MoE every other)
# ===========================================================================

def _hybrid_layout(cfg: ModelConfig):
    P = cfg.attn_period
    assert cfg.n_layers % P == 0, "hybrid n_layers must divide attn_period"
    moe_slots = [j for j in range(P) if j % cfg.moe_every == cfg.moe_every - 1]
    dense_slots = [j for j in range(P) if j not in moe_slots]
    return cfg.n_layers // P, P, moe_slots, dense_slots


def init_hybrid(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    n_periods, P, moe_slots, dense_slots = _hybrid_layout(cfg)
    ks = jax.random.split(key, 8)

    def period_init(k):
        kk = jax.random.split(k, 4)
        return {
            "attn_ln": init_norm(cfg.d_model, cfg.norm, dtype),
            "attn": init_attention(kk[0], cfg, dtype),
            "mamba_ln": _stacked_init(
                kk[1], P - 1, lambda _k: init_norm(cfg.d_model, cfg.norm, dtype)),
            "mamba": _stacked_init(
                kk[1], P - 1, lambda _k: init_mamba(_k, cfg, dtype)),
            "ffn_dense_ln": _stacked_init(
                kk[2], len(dense_slots),
                lambda _k: init_norm(cfg.d_model, cfg.norm, dtype)),
            "ffn_dense": _stacked_init(
                kk[2], len(dense_slots), lambda _k: init_mlp(_k, cfg, dtype)),
            "ffn_moe_ln": _stacked_init(
                kk[3], len(moe_slots),
                lambda _k: init_norm(cfg.d_model, cfg.norm, dtype)),
            "ffn_moe": _stacked_init(
                kk[3], len(moe_slots), lambda _k: init_moe(_k, cfg, dtype)),
        }

    params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "periods": _stacked_init(ks[1], n_periods, period_init),
        "final_norm": init_norm(cfg.d_model, cfg.norm, dtype),
        "head": (jax.random.normal(ks[2], (cfg.d_model, cfg.vocab),
                                   jnp.float32)
                 * cfg.d_model ** -0.5).astype(dtype),
    }
    return params


def _hybrid_period(cfg: ModelConfig, pp: Params, x, positions, *,
                   caches=None, cache_pos=None):
    """One period: sublayer 0 attention, 1..P-1 mamba; FFN after each mixer.

    caches (decode): dict {kv_k, kv_v, conv (P-1,...), ssm (P-1,...)}; the
    kv slices are read only. Returns (x, aux, new_caches): at prefill the
    whole k/v, at decode the new k/v row (the caller writes it) and the
    updated mamba states.
    """
    _, P, moe_slots, dense_slots = _hybrid_layout(cfg)
    aux = jnp.float32(0.0)
    new = {}
    mamba_conv, mamba_ssm = [], []
    d_i = m_i = 0
    for j in range(P):
        if j == 0:
            xn = norm(pp["attn_ln"], x, cfg.norm)
            if caches is None:
                h, kv = attention(pp["attn"], xn, cfg, positions=positions,
                                  window=cfg.window or None)
            else:
                h, kv = attention(pp["attn"], xn, cfg, positions=positions,
                                  cache=(caches["kv_k"], caches["kv_v"]),
                                  cache_pos=cache_pos,
                                  window=cfg.window or None)
            new["kv_k"], new["kv_v"] = kv
            x = x + h
        else:
            lp = jax.tree.map(lambda a, _j=j: a[_j - 1], pp["mamba"])
            ln = jax.tree.map(lambda a, _j=j: a[_j - 1], pp["mamba_ln"])
            xn = norm(ln, x, cfg.norm)
            if caches is None:
                h, state = mamba_prefill(lp, xn, cfg)
            else:
                h, state = mamba_decode(
                    lp, xn, cfg,
                    (caches["conv"][j - 1], caches["ssm"][j - 1]))
            mamba_conv.append(state[0])
            mamba_ssm.append(state[1])
            x = x + h
        if j in moe_slots:
            ln = jax.tree.map(lambda a, _i=m_i: a[_i], pp["ffn_moe_ln"])
            fp = jax.tree.map(lambda a, _i=m_i: a[_i], pp["ffn_moe"])
            f, a = moe(fp, norm(ln, x, cfg.norm), cfg)
            aux = aux + a
            m_i += 1
        else:
            ln = jax.tree.map(lambda a, _i=d_i: a[_i], pp["ffn_dense_ln"])
            fp = jax.tree.map(lambda a, _i=d_i: a[_i], pp["ffn_dense"])
            f = mlp(fp, norm(ln, x, cfg.norm), cfg)
            d_i += 1
        x = x + f
    new["conv"] = jnp.stack(mamba_conv)
    new["ssm"] = jnp.stack(mamba_ssm)
    return x, aux, new


def hybrid_forward(params: Params, cfg: ModelConfig, tokens,
                   want_cache: bool = False):
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(carry, pp):
        x, aux = carry
        x, a, caches = _hybrid_period(cfg, pp, x, positions)
        return (x, aux + a), (caches if want_cache else None)

    body = _remat(body, cfg)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                    params["periods"])
    x = norm(params["final_norm"], x, cfg.norm)
    logits = x @ params["head"]
    return logits, aux, caches


def hybrid_init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    dtype = jnp.dtype(cfg.dtype)
    n_periods, P, _, _ = _hybrid_layout(cfg)
    conv_s, ssm_s = mamba_state_shapes(cfg, batch)
    kv_len = min(max_seq, cfg.window) if cfg.window else max_seq
    kv = jnp.zeros((n_periods, batch, cfg.n_kv_heads, kv_len, cfg.d_head),
                   dtype)
    return {"kv_k": kv, "kv_v": kv,
            "conv": jnp.zeros((n_periods, P - 1) + conv_s, dtype),
            "ssm": jnp.zeros((n_periods, P - 1) + ssm_s, dtype),
            "pos": jnp.int32(0)}


def hybrid_prefill(params: Params, cfg: ModelConfig, tokens, max_seq: int):
    B, S = tokens.shape
    logits, _, caches = hybrid_forward(params, cfg, tokens, want_cache=True)
    kv_len = min(max_seq, cfg.window) if cfg.window else max_seq
    pad = kv_len - min(S, kv_len)
    k, v = (_pad_seq(_head_major(caches[n])[:, :, :, -kv_len:], pad)
            for n in ("kv_k", "kv_v"))
    cache = {"kv_k": k, "kv_v": v, "conv": caches["conv"],
             "ssm": caches["ssm"], "pos": jnp.int32(S)}
    return logits[:, -1], cache


def hybrid_decode(params: Params, cfg: ModelConfig, tokens, cache):
    B = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    pos = cache["pos"]
    kv_len = cache["kv_k"].shape[3]
    write_pos = jnp.minimum(pos, kv_len - 1)   # ring-ish clamp for window
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)

    def body(carry, xs):
        x, aux = carry
        pp, conv, ssm_state, period = xs
        caches = {"kv_k": _layer(cache["kv_k"], period),
                  "kv_v": _layer(cache["kv_v"], period),
                  "conv": conv, "ssm": ssm_state}
        x, a, new = _hybrid_period(cfg, pp, x, positions, caches=caches,
                                   cache_pos=write_pos)
        return (x, aux + a), new

    (x, _), new = jax.lax.scan(
        body, (x, jnp.float32(0.0)),
        (params["periods"], cache["conv"], cache["ssm"],
         jnp.arange(cache["kv_k"].shape[0])))
    x = norm(params["final_norm"], x, cfg.norm)
    logits = x @ params["head"]
    new_cache = {"kv_k": _write_rows(cache["kv_k"], new["kv_k"], write_pos),
                 "kv_v": _write_rows(cache["kv_v"], new["kv_v"], write_pos),
                 "conv": new["conv"], "ssm": new["ssm"], "pos": pos + 1}
    return logits[:, -1], new_cache


# ===========================================================================
# encdec program (whisper: encoder + causal decoder w/ cross-attention)
# ===========================================================================

def init_encdec(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    bias = True   # whisper uses biased projections

    def enc_layer(k):
        kk = jax.random.split(k, 2)
        return {"ln1": init_norm(cfg.d_model, cfg.norm, dtype),
                "attn": init_attention(kk[0], cfg, dtype, bias=bias),
                "ln2": init_norm(cfg.d_model, cfg.norm, dtype),
                "ffn": init_mlp(kk[1], cfg, dtype, bias=bias)}

    def dec_layer(k):
        kk = jax.random.split(k, 3)
        return {"ln1": init_norm(cfg.d_model, cfg.norm, dtype),
                "attn": init_attention(kk[0], cfg, dtype, bias=bias),
                "ln_x": init_norm(cfg.d_model, cfg.norm, dtype),
                "cross": init_attention(kk[1], cfg, dtype, bias=bias),
                "ln2": init_norm(cfg.d_model, cfg.norm, dtype),
                "ffn": init_mlp(kk[2], cfg, dtype, bias=bias)}

    return {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dtype),
        "enc_layers": _stacked_init(ks[1], cfg.encoder_layers, enc_layer),
        "enc_norm": init_norm(cfg.d_model, cfg.norm, dtype),
        "dec_layers": _stacked_init(ks[2], cfg.n_layers, dec_layer),
        "final_norm": init_norm(cfg.d_model, cfg.norm, dtype),
    }


def encdec_encode(params: Params, cfg: ModelConfig, frames):
    """frames: (B, S_enc, d_model) — precomputed conv-frontend embeddings."""
    B, S, _ = frames.shape
    x = frames.astype(jnp.dtype(cfg.dtype)) \
        + sinusoid_positions(S, cfg.d_model, jnp.dtype(cfg.dtype))[None]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(x, lp):
        h, _ = attention(lp["attn"], norm(lp["ln1"], x, cfg.norm), cfg,
                         positions=positions, causal=False, use_rope=False)
        x = x + h
        x = x + mlp(lp["ffn"], norm(lp["ln2"], x, cfg.norm), cfg)
        return x, None

    body = _remat(body, cfg)
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return norm(params["enc_norm"], x, cfg.norm)


def _encdec_cross_kvs(params: Params, cfg: ModelConfig, enc_out):
    """Precompute per-decoder-layer cross K/V: (L, B, S_enc, KH, D) x2."""
    def one(lp):
        return cross_kv(lp["cross"], cfg, enc_out)
    return jax.lax.map(one, params["dec_layers"])


def encdec_forward(params: Params, cfg: ModelConfig, frames, tokens,
                   want_cache: bool = False):
    """Teacher-forced: encode frames, decode tokens. Returns (logits, aux, kvs)."""
    enc_out = encdec_encode(params, cfg, frames)
    B, S = tokens.shape
    dtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens].astype(dtype) \
        + sinusoid_positions(S, cfg.d_model, dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(carry, lp):
        x = carry
        h, kv = attention(lp["attn"], norm(lp["ln1"], x, cfg.norm), cfg,
                          positions=positions, causal=True, use_rope=False)
        x = x + h
        ckv = cross_kv(lp["cross"], cfg, enc_out)
        x = x + cross_attention(lp["cross"], norm(lp["ln_x"], x, cfg.norm),
                                cfg, ckv)
        x = x + mlp(lp["ffn"], norm(lp["ln2"], x, cfg.norm), cfg)
        return x, ((kv, ckv) if want_cache else None)

    body = _remat(body, cfg)
    x, kvs = jax.lax.scan(body, x, params["dec_layers"])
    x = norm(params["final_norm"], x, cfg.norm)
    logits = x @ params["embed"].T.astype(x.dtype)   # whisper ties embeddings
    return logits, jnp.float32(0.0), kvs


def encdec_init_cache(cfg: ModelConfig, batch: int, max_seq: int,
                      dec_len: int = 448):
    dtype = jnp.dtype(cfg.dtype)
    kv = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, dec_len, cfg.d_head),
                   dtype)
    cross = jnp.zeros((cfg.n_layers, batch, max_seq, cfg.n_kv_heads,
                       cfg.d_head), dtype)
    return {"k": kv, "v": kv, "cross_k": cross, "cross_v": cross,
            "pos": jnp.int32(0)}


def encdec_prefill(params: Params, cfg: ModelConfig, frames, tokens,
                   dec_len: int = 448):
    """Encode audio + run the decoder prompt; cache self KV + cross KV."""
    logits, _, kvs = encdec_forward(params, cfg, frames, tokens,
                                    want_cache=True)
    (k, v), (ck, cv) = kvs
    S = tokens.shape[1]
    k, v = (_pad_seq(_head_major(kv), dec_len - S) for kv in (k, v))
    cache = {"k": k, "v": v, "cross_k": ck, "cross_v": cv,
             "pos": jnp.int32(S)}
    return logits[:, -1], cache


def encdec_decode(params: Params, cfg: ModelConfig, tokens, cache):
    B = tokens.shape[0]
    dtype = jnp.dtype(cfg.dtype)
    pos = cache["pos"]
    x = params["embed"][tokens].astype(dtype)
    x = x + sinusoid_positions(448, cfg.d_model, dtype)[pos][None, None]
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)

    def body(carry, xs):
        x = carry
        lp, layer = xs
        kv = (_layer(cache["k"], layer), _layer(cache["v"], layer))
        h, rows = attention(lp["attn"], norm(lp["ln1"], x, cfg.norm), cfg,
                            positions=positions, cache=kv, cache_pos=pos,
                            use_rope=False)
        x = x + h
        cross = (_layer(cache["cross_k"], layer),
                 _layer(cache["cross_v"], layer))
        x = x + cross_attention(lp["cross"], norm(lp["ln_x"], x, cfg.norm),
                                cfg, cross)
        x = x + mlp(lp["ffn"], norm(lp["ln2"], x, cfg.norm), cfg)
        return x, rows

    x, (nks, nvs) = jax.lax.scan(
        body, x, (params["dec_layers"], jnp.arange(cfg.n_layers)))
    x = norm(params["final_norm"], x, cfg.norm)
    logits = x @ params["embed"].T.astype(x.dtype)
    new_cache = {"k": _write_rows(cache["k"], nks, pos),
                 "v": _write_rows(cache["v"], nvs, pos),
                 "cross_k": cache["cross_k"], "cross_v": cache["cross_v"],
                 "pos": pos + 1}
    return logits[:, -1], new_cache


# ===========================================================================
# mixed-cache decode (gemma3 local:global — §Perf P3 optimization)
# ===========================================================================
#
# Baseline decode allocates a seq-length KV cache for EVERY layer; in a 5:1
# local:global model only the global layers need it — local layers attend to
# a (window)-token sliding window. This path gives local layers a *ring*
# cache of W slots (write at pos % W; rope is applied at write time so slot
# order is irrelevant to attention). At long_500k this shrinks the cache
# ~6.5x and the per-step HBM traffic with it. The layer loop is unrolled
# (heterogeneous cache shapes can't ride one scan); fine for gemma3's size.

def _lg_layout(cfg: ModelConfig):
    idx = np.arange(cfg.n_layers)
    is_global = (idx + 1) % cfg.local_global_period == 0
    return is_global


def decoder_init_cache_mixed(cfg: ModelConfig, batch: int, max_seq: int):
    assert cfg.local_global_period and cfg.window
    dtype = jnp.dtype(cfg.dtype)
    is_global = _lg_layout(cfg)
    n_glob = int(is_global.sum())
    n_loc = cfg.n_layers - n_glob
    glob = jnp.zeros((n_glob, batch, cfg.n_kv_heads, max_seq, cfg.d_head),
                     dtype)
    loc = jnp.zeros((n_loc, batch, cfg.n_kv_heads, cfg.window, cfg.d_head),
                    dtype)
    return {"k_global": glob, "v_global": glob, "k_local": loc,
            "v_local": loc, "pos": jnp.int32(0)}


def decoder_decode_mixed(params: Params, cfg: ModelConfig, tokens, cache):
    B = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    pos = cache["pos"]
    W = cfg.window
    positions = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    is_global = _lg_layout(cfg)
    ring_pos = jnp.mod(pos, W)
    ring_len = jnp.minimum(pos + 1, W)

    new_g_k, new_g_v, new_l_k, new_l_v = [], [], [], []
    gi = li = 0
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a, _l=layer: a[_l], params["layers"])
        xn = norm(lp["ln1"], x, cfg.norm)
        if is_global[layer]:
            ck, cv = cache["k_global"][gi], cache["v_global"][gi]
            h, (nk, nv) = attention(lp["attn"], xn, cfg, positions=positions,
                                    cache=(ck, cv), cache_pos=pos)
            new_g_k.append(nk)
            new_g_v.append(nv)
            gi += 1
        else:
            ck, cv = cache["k_local"][li], cache["v_local"][li]
            h, (nk, nv) = attention(lp["attn"], xn, cfg, positions=positions,
                                    cache=(ck, cv), cache_pos=ring_pos,
                                    cache_length=ring_len)
            new_l_k.append(nk)
            new_l_v.append(nv)
            li += 1
        x = x + h
        x = x + mlp(lp["ffn"], norm(lp["ln2"], x, cfg.norm), cfg)

    x = norm(params["final_norm"], x, cfg.norm)
    head = params.get("head")
    logits = x @ (head if head is not None else params["embed"].T.astype(x.dtype))
    new_cache = {
        "k_global": _write_rows(cache["k_global"], jnp.stack(new_g_k), pos),
        "v_global": _write_rows(cache["v_global"], jnp.stack(new_g_v), pos),
        "k_local": _write_rows(cache["k_local"], jnp.stack(new_l_k), ring_pos),
        "v_local": _write_rows(cache["v_local"], jnp.stack(new_l_v), ring_pos),
        "pos": pos + 1}
    return logits[:, -1], new_cache
