"""Seeded weights of a Qwen3 dense decoder, and its plain float32 reference.

The benchmark makes the weights: ``make_params`` draws every leaf from the
seed on the device in one jitted call, in the dtype they are served in, laid
out as the served program expects them (layer leaves stacked on a leading
axis). Each leaf, and each layer of a stacked leaf, has a key of its own, so
the reference draws any one layer again bit for bit, from the seed alone.

The reference follows the published Qwen3 block (pre-norm RMSNorm, grouped
query attention with RMSNorm on queries and keys before a half-split rotary
embedding, SiLU-gated MLP, final RMSNorm, untied head) in float32 at the
highest matmul precision, one layer at a time, with no cache and no
batching. ``quant="fp8"`` is the control: every projection and the head
multiply operands rounded to float8 e4m3 (per-token and per-output-channel
scales), the precision below the bfloat16 the configuration states.
"""
from __future__ import annotations

import zlib
from typing import Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# leaf path -> (shape from the config, kind); kind sets the distribution
_LAYER_LEAVES = {
    "ln1/scale": (lambda c: (c.d,), "norm"),
    "attn/wq/w": (lambda c: (c.d, c.h * c.dh), "proj"),
    "attn/wk/w": (lambda c: (c.d, c.kh * c.dh), "proj"),
    "attn/wv/w": (lambda c: (c.d, c.kh * c.dh), "proj"),
    "attn/wo/w": (lambda c: (c.h * c.dh, c.d), "proj"),
    "attn/q_norm/scale": (lambda c: (c.dh,), "norm"),
    "attn/k_norm/scale": (lambda c: (c.dh,), "norm"),
    "ln2/scale": (lambda c: (c.d,), "norm"),
    "ffn/up/w": (lambda c: (c.d, c.f), "proj"),
    "ffn/gate/w": (lambda c: (c.d, c.f), "proj"),
    "ffn/down/w": (lambda c: (c.f, c.d), "proj"),
}
_TOP_LEAVES = {
    "embed": (lambda c: (c.v, c.d), "embed"),
    "final_norm/scale": (lambda c: (c.d,), "norm"),
    "head": (lambda c: (c.d, c.v), "proj"),
}
FP8_MAX = 448.0


class Shapes:
    def __init__(self, cfg: Mapping):
        self.d = cfg["hidden_size"]
        self.f = cfg["intermediate_size"]
        self.h = cfg["num_attention_heads"]
        self.kh = cfg["num_key_value_heads"]
        self.dh = cfg["head_dim"]
        self.v = cfg["vocab_size"]
        self.layers = cfg["num_hidden_layers"]
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.dtype = jnp.dtype(cfg["torch_dtype"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed."""
    words = np.random.SeedSequence([seed, 0x3E16]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _draw(key, path: str, shape, kind: str, dtype, layer: Optional[int]):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "norm":
        x = 1.0 + 0.1 * z
    elif kind == "embed":
        x = z
    else:
        x = z * shape[0] ** -0.5
    return x.astype(dtype)


def _nest(flat: Mapping[str, jax.Array]) -> dict:
    out: dict = {}
    for path, x in flat.items():
        *parents, leaf = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return out


def layer_weights(key, s: Shapes, layer: int, dtype) -> Dict[str, jax.Array]:
    """One layer's leaves, flat by path."""
    return {p: _draw(key, "layers/" + p, shp(s), kind, dtype, layer)
            for p, (shp, kind) in _LAYER_LEAVES.items()}


def top_weight(key, s: Shapes, path: str, dtype) -> jax.Array:
    shp, kind = _TOP_LEAVES[path]
    return _draw(key, path, shp(s), kind, dtype, None)


def make_params(cfg: Mapping, seed: int):
    """Every weight of the served model, drawn on the device from ``seed``
    in one jitted call, in the served dtype."""
    s = Shapes(cfg)

    def build(key):
        per_layer = [layer_weights(key, s, i, s.dtype) for i in range(s.layers)]
        flat = {"layers/" + p: jnp.stack([w[p] for w in per_layer])
                for p in _LAYER_LEAVES}
        flat.update({p: top_weight(key, s, p, s.dtype) for p in _TOP_LEAVES})
        return _nest(flat)

    return jax.jit(build)(seed_key(seed))


# -- the reference forward ----------------------------------------------------

def _fp8(x, axis: int):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant: Optional[str]):
    """x (..., K) @ w (K, N), in float32 or with fp8-rounded operands."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x: (S, heads, D); positions 0..S-1, half-split rotation."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(s: Shapes, w: Mapping[str, jax.Array], x, quant):
    """One block over one sequence x: (S, d), float32."""
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    S = x.shape[0]
    xn = _rms(x, f32["ln1/scale"], s.eps)
    q = _mm(xn, f32["attn/wq/w"], quant).reshape(S, s.h, s.dh)
    k = _mm(xn, f32["attn/wk/w"], quant).reshape(S, s.kh, s.dh)
    v = _mm(xn, f32["attn/wv/w"], quant).reshape(S, s.kh, s.dh)
    q = _rope(_rms(q, f32["attn/q_norm/scale"], s.eps), s.theta)
    k = _rope(_rms(k, f32["attn/k_norm/scale"], s.eps), s.theta)
    kv_of = jnp.arange(s.h) // (s.h // s.kh)        # query head -> kv head
    scores = jnp.einsum("shd,thd->hst", q, k[:, kv_of]) * s.dh ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,thd->shd", probs, v[:, kv_of]).reshape(S, s.h * s.dh)
    x = x + _mm(o, f32["attn/wo/w"], quant)
    xn = _rms(x, f32["ln2/scale"], s.eps)
    g = jax.nn.silu(_mm(xn, f32["ffn/gate/w"], quant))
    return x + _mm(g * _mm(xn, f32["ffn/up/w"], quant), f32["ffn/down/w"],
                   quant)


def _bucket(n: int, step: int = 512) -> int:
    return -(-n // step) * step


def reference_pick(cfg: Mapping, seed: int, seqs: Sequence[np.ndarray],
                   tokens: Sequence[np.ndarray], quant: Optional[str] = None
                   ) -> list:
    """Run the reference over each token sequence, drawing the weights from
    ``seed`` layer by layer, and read its logits at the last
    ``len(tokens[i])`` positions: returns per sequence (best logit, logit of
    ``tokens[i]``, argmax), each an array over those positions."""
    s = Shapes(cfg)
    key = seed_key(seed)
    with jax.default_matmul_precision("highest"):
        embed = jax.jit(lambda k: top_weight(k, s, "embed", s.dtype))(key)
        hs, picks = [], []
        for toks, want in zip(seqs, tokens):
            S = _bucket(len(toks))
            padded = np.zeros(S, np.int32)
            padded[:len(toks)] = toks
            hs.append(embed[jnp.asarray(padded)].astype(jnp.float32))
            pick = np.zeros(S, np.int32)
            pick[len(toks) - len(want):len(toks)] = want
            picks.append(pick)
        del embed
        layer_fn = jax.jit(lambda w, x: _layer(s, w, x, quant))
        draw = jax.jit(lambda k, i: layer_weights(k, s, i, s.dtype))
        for i in range(s.layers):
            w = draw(key, i)
            hs = [layer_fn(w, x) for x in hs]
            del w
        final = jax.jit(lambda k: top_weight(k, s, "final_norm/scale",
                                             s.dtype))(key)
        head = jax.jit(lambda k: top_weight(k, s, "head", s.dtype))(key)

        @jax.jit
        def read(x, pick, final, head):
            logits = _mm(_rms(x, final.astype(jnp.float32), s.eps),
                         head.astype(jnp.float32), quant)
            chosen = jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
            return logits.max(-1), chosen, jnp.argmax(logits, -1)

        out = []
        for x, pick, toks, want in zip(hs, picks, seqs, tokens):
            lo = len(toks) - len(want)
            best, chosen, top = (np.asarray(a)[lo:len(toks)] for a in
                                 read(x, jnp.asarray(pick), final, head))
            out.append((best, chosen, top))
        return out


def widest_gap(picks) -> float:
    """How far the chosen tokens' reference logits lie below the reference's
    best, at the widest over every position of every sequence."""
    return float(max(np.max(best - chosen) for best, chosen, _ in picks))
