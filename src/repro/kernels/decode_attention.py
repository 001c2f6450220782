"""Pallas TPU decode attention — one new token against a long KV cache.

Decode is memory-bound: the kernel's job is to stream the head-major
(KH, S, D) cache through VMEM exactly once at full HBM bandwidth while the
tiny (KH, G, D) query tile stays resident. Grid: (B, ns) with the
sequence-block axis innermost; each step loads one (KH, bs, D) block
holding every kv head, and an unrolled loop over the KH heads runs the
online softmax of that head's G query heads on its (bs, D) rows. The
acc/m/l scratch carries across blocks, exactly like flash attention.

``length``/``start`` arrive as one (B, 2) i32 operand in SMEM (traced —
they change every step; recompiling per position would be absurd). Its
block is the whole array, so a vmap over serving slots, which adds a grid
axis, still satisfies the tiling rules. Blocks
wholly outside [start, length) still stream (baseline; skipping them via
the grid needs scalar-prefetched lengths).

Oracle: kernels/ref.py::decode_attention_reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _da_kernel(bounds_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
               *, scale: float, block_s: int, ns: int, n_kv: int):
    b = pl.program_id(0)
    isb = pl.program_id(1)
    length = bounds_ref[b, 0]
    start = bounds_ref[b, 1]

    @pl.when(isb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = isb * block_s + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_s), 1)                    # (1, bs)
    mask = (pos < length) & (pos >= start)             # (1, bs)
    for h in range(n_kv):
        q = q_ref[0, h]                                # (G, D)
        k = k_ref[0, h]                                # (bs, D)
        v = v_ref[0, h]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)                # (G, bs)
        m_prev = m_ref[h]                              # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(isb == ns - 1)
    def _fin():
        for h in range(n_kv):
            l = jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, length, start=0, *,
                            block_s: int = 512, interpret: bool = False):
    """q: (B, H, D); caches: (B, KH, S, D); attend to slots [start, length).

    Returns (B, H, D).
    """
    B, H, D = q.shape
    KH, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    bs = min(block_s, S)
    assert S % bs == 0, (S, bs)
    ns = S // bs
    qg = q.reshape(B, KH, G, D)
    bounds = jnp.broadcast_to(jnp.stack([jnp.asarray(length, jnp.int32),
                                         jnp.asarray(start, jnp.int32)]),
                              (B, 2))

    kernel = functools.partial(_da_kernel, scale=D ** -0.5, block_s=bs,
                               ns=ns, n_kv=KH)
    out = pl.pallas_call(
        kernel,
        grid=(B, ns),
        in_specs=[
            pl.BlockSpec((B, 2), lambda b, isb: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, KH, G, D), lambda b, isb: (b, 0, 0, 0)),
            pl.BlockSpec((1, KH, bs, D), lambda b, isb: (b, 0, isb, 0)),
            pl.BlockSpec((1, KH, bs, D), lambda b, isb: (b, 0, isb, 0)),
        ],
        out_specs=pl.BlockSpec((1, KH, G, D), lambda b, isb: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KH, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((KH, G, D), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
            pltpu.VMEM((KH, G, 1), jnp.float32),
        ],
        interpret=interpret,
    )(bounds, qg, k_cache, v_cache)
    return out.reshape(B, H, D)
