"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantics of record: kernels/tests assert allclose against
them, and models fall back to them when ``*_impl="reference"`` (e.g. the
dry-run, which lowers for a TPU-less CPU backend).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# -- flash attention (prefill) ---------------------------------------------------

def flash_attention_reference(q, k, v, *, causal: bool = True,
                              window: int = 0):
    """q: (B,H,S,D); k,v: (B,KH,T,D) with H = KH*G. Returns (B,H,S,D)."""
    B, H, S, D = q.shape
    KH, T = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, S, D)
    scores = jnp.einsum("bkgsd,bktd->bkgst", qg, k).astype(jnp.float32)
    scores = scores * (D ** -0.5)
    qpos = jnp.arange(S)[:, None] + (T - S)     # right-aligned query positions
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= qpos >= kpos
    if window > 0:
        mask &= qpos - kpos < window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,bktd->bkgsd", probs, v)
    return out.reshape(B, H, S, D)


# -- decode attention (one new token vs long KV) -----------------------------------

def decode_attention_reference(q, k_cache, v_cache, length, start=0):
    """q: (B,H,D); caches: (B,KH,S,D); attend to cache slots [start, length).

    Returns (B,H,D). ``length``/``start`` may be traced scalars (local
    windows pass start = length - window).
    """
    B, H, D = q.shape
    KH, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, D)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache).astype(jnp.float32)
    scores = scores * (D ** -0.5)
    pos = jnp.arange(S)[None, :]
    mask = (pos < length) & (pos >= start)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v_cache)
    return out.reshape(B, H, D)


# -- Mamba2 SSD (state-space duality) chunked scan ----------------------------------

def _segsum(x):
    """(..., T) -> (..., T, T) lower-triangular segment sums."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    ss = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, ss, -jnp.inf)


def ssd_reference(x, dt, A, B, C, *, chunk: int = 128,
                  initial_state: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD (Mamba-2, arXiv:2405.21060 Listing 1) with dt folded in.

    x:  (b, l, h, p)   input sequences per head
    dt: (b, l, h)      positive step sizes (softplus'd upstream)
    A:  (h,)           negative per-head decay
    B:  (b, l, n)      input projection (single group, shared across heads)
    C:  (b, l, n)      output projection
    Returns (y: (b,l,h,p), final_state: (b,h,p,n)).
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    assert l % chunk == 0, f"sequence {l} not divisible by chunk {chunk}"
    c = l // chunk

    dA = dt * A[None, None, :]                      # (b, l, h)
    xd = x * dt[..., None]                          # dt-weighted input

    # reshape into chunks
    xd = xd.reshape(b, c, chunk, h, p)
    dA = dA.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)       # (b,h,c,s)
    Bc = B.reshape(b, c, chunk, n)
    Cc = C.reshape(b, c, chunk, n)
    dA_cs = jnp.cumsum(dA, axis=-1)                              # (b,h,c,s)

    # 1. intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(dA))                                     # (b,h,c,s,s)
    Y_diag = jnp.einsum("bcsn,bczn,bhcsz,bczhp->bcshp", Cc, Bc, L, xd)

    # 2. chunk-final states
    decay_states = jnp.exp(dA_cs[..., -1:] - dA_cs)              # (b,h,c,s)
    states = jnp.einsum("bczn,bhcz,bczhp->bchpn", Bc, decay_states, xd)

    # 3. inter-chunk recurrence (scan over chunk-final states)
    chunk_decay = jnp.exp(dA_cs[..., -1])                        # (b,h,c)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), x.dtype)

    def step(carry, inp):
        s_new, decay = inp                                       # (b,h,p,n),(b,h)
        carry = carry * decay[..., None, None] + s_new
        return carry, carry

    states_t = states.transpose(1, 0, 2, 3, 4)                   # (c,b,h,p,n)
    decay_t = chunk_decay.transpose(2, 0, 1)                     # (c,b,h)
    final, all_states = jax.lax.scan(step, initial_state.astype(jnp.float32),
                                     (states_t.astype(jnp.float32), decay_t))
    # state *entering* each chunk
    prev_states = jnp.concatenate(
        [initial_state.astype(jnp.float32)[None], all_states[:-1]], axis=0)
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)           # (b,c,h,p,n)

    # 4. state -> output
    state_decay = jnp.exp(dA_cs)                                 # (b,h,c,s)
    Y_off = jnp.einsum("bcsn,bchpn,bhcs->bcshp", Cc,
                       prev_states.astype(x.dtype), state_decay.astype(x.dtype))

    y = (Y_diag + Y_off).reshape(b, l, h, p)
    return y.astype(x.dtype), final.astype(x.dtype)


def ssd_decode_reference(x, dt, A, B, C, state):
    """One recurrent SSD step.

    x: (b,h,p); dt: (b,h); A: (h,); B,C: (b,n); state: (b,h,p,n).
    h_t = exp(dt A) h_{t-1} + dt * x ⊗ B ;  y = h_t · C
    """
    dA = jnp.exp(dt * A[None, :])                                # (b,h)
    upd = (dt[..., None] * x)[..., None] * B[:, None, None, :]   # (b,h,p,n)
    state = state * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, C)
    return y.astype(x.dtype), state


# -- RASK batched objective (autoscaler Eq. (4) inner evaluation) -------------

def rask_objective_reference(A, rel_gather, w, exponents, term_mask, x_scale,
                             slo_kind, slo_service, slo_weight, slo_target,
                             slo_pidx, slo_ridx, rps, *, n_services: int,
                             max_degree: int):
    """Per-service weighted SLO fulfillment for K candidate assignments.

    A:          (K, D)        candidate decision vectors (raw parameter units)
    rel_gather: (R, F)  int32 indices of each relation's features in a
    w:          (R, T)        stacked polynomial weights (0 on padded terms)
    exponents:  (R, T, F) int32 term exponent tables (0 on padding)
    term_mask:  (R, T)        1.0 real term / 0.0 padding
    x_scale:    (R, F)        feature conditioning (1.0 on padding)
    slo_kind:   (Q,) int32    0 = parameter metric, 1 = completion, 2 = relation
    slo_service/slo_weight/slo_target: (Q,) per-SLO service index/weight/target
    slo_pidx:   (Q,) int32    decision index of the metric (kind 0)
    slo_ridx:   (Q,) int32    relation index of the metric (kinds 1 and 2)
    rps:        (S,)          per-service request load

    Returns (K, n_services): sum of weight * min(metric/target, 1) per service,
    where the completion SLO (kind 1) reads min(pred / (rps * target), 1).
    Powers are built by cumulative products + gather (no ``jnp.power``), the
    same multiplication order as core/regression's expansion.
    """
    A = jnp.asarray(A, jnp.float32)
    r_count, t_count, f_count = exponents.shape

    def predict(a):
        xs = a[rel_gather] / x_scale                              # (R, F)
        if max_degree:
            pows = jnp.cumprod(jnp.broadcast_to(
                xs[:, None, :], (r_count, max_degree, f_count)), axis=1)
            pows = jnp.concatenate(
                [jnp.ones((r_count, 1, f_count), xs.dtype), pows], axis=1)
        else:
            pows = jnp.ones((r_count, 1, f_count), xs.dtype)
        vals = jnp.take_along_axis(
            jnp.broadcast_to(pows[:, None],
                             (r_count, t_count, max_degree + 1, f_count)),
            exponents[:, :, None, :], axis=2)[:, :, 0, :]
        terms = jnp.prod(vals, axis=-1) * term_mask               # (R, T)
        return jnp.sum(terms * w, axis=-1)                        # (R,)

    def one(a):
        preds = predict(a)
        numer = jnp.where(slo_kind == 0, a[slo_pidx], preds[slo_ridx])
        denom = jnp.where(slo_kind == 1,
                          jnp.maximum(rps[slo_service] * slo_target, 1e-9),
                          slo_target)
        phi = jnp.minimum(numer / denom, 1.0)
        return jax.ops.segment_sum(slo_weight * phi, slo_service,
                                   num_segments=n_services)

    return jax.vmap(one)(A)


# -- memory-efficient chunked attention (flash-style, pure jnp) ---------------
#
# The reference full-mask attention materializes (S, T) score matrices —
# fine as an oracle at test shapes, physically impossible at 32k. This is
# the O(S) -memory double-scan with online softmax and a custom VJP that
# recomputes tiles in the backward pass (the same algorithm the Pallas
# kernel implements on TPU VMEM tiles). Supports GQA, causal and (possibly
# traced) sliding windows.

from functools import partial as _partial


def _chunk_mask(q0, k0, cq, ck, S, T, causal, window):
    """window: traced f32 scalar (inf = unbounded)."""
    qpos = q0 + jnp.arange(cq)[:, None] + (T - S)       # right-aligned
    kpos = k0 + jnp.arange(ck)[None, :]
    m = (qpos - kpos).astype(jnp.float32) < window
    if causal:
        m &= qpos >= kpos
    return m


def _ca_fwd_impl(q, k, v, window, causal, q_chunk, k_chunk):
    B, S, KH, G, D = q.shape
    T = k.shape[1]
    cq = min(q_chunk, S)
    ck = min(k_chunk, T)
    nq, nk = S // cq, T // ck
    scale = D ** -0.5
    qc = q.reshape(B, nq, cq, KH, G, D).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, ck, KH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, KH, D).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_q0):
        qi, q0 = qi_q0

        def kv_step(carry, inp):
            acc, m, l = carry
            ki, vi, k0 = inp
            s = jnp.einsum("bqkgd,bckd->bkgqc", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            msk = _chunk_mask(q0, k0, cq, ck, S, T, causal, window)
            s = jnp.where(msk[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bckd->bkgqd", p.astype(v.dtype), vi,
                preferred_element_type=jnp.float32)
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, KH, G, cq, D), jnp.float32)
        m0 = jnp.full((B, KH, G, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KH, G, cq), jnp.float32)
        k0s = jnp.arange(nk) * ck
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0),
                                      (kc, vc, k0s))
        l = jnp.maximum(l, 1e-30)
        out = (acc / l[..., None]).astype(q.dtype)      # (B,KH,G,cq,D)
        lse = m + jnp.log(l)
        return None, (out, lse)

    q0s = jnp.arange(nq) * cq
    _, (outs, lses) = jax.lax.scan(q_step, None, (qc, q0s))
    # outs: (nq, B, KH, G, cq, D) -> (B, S, KH, G, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, KH, G, D)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, KH, G, S)
    return out, lse


@_partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _chunked_attention(q, k, v, window, causal, q_chunk, k_chunk):
    out, _ = _ca_fwd_impl(q, k, v, window, causal, q_chunk, k_chunk)
    return out


def chunked_attention(q, k, v, causal=True, window=None,
                      q_chunk: int = 512, k_chunk: int = 1024):
    """Flash-style attention. q: (B,S,KH,G,D); k,v: (B,T,KH,D).

    Returns (B,S,KH,G,D). O(S) memory in both passes; the VJP recomputes
    tiles instead of saving the (S,T) score matrix. ``window`` may be None
    (unbounded), a static int, or a traced scalar (gemma3 local/global).
    """
    w = jnp.float32(jnp.inf) if window is None \
        else jnp.asarray(window, jnp.float32)
    return _chunked_attention(q, k, v, w, causal, q_chunk, k_chunk)


def _ca_fwd(q, k, v, window, causal, q_chunk, k_chunk):
    out, lse = _ca_fwd_impl(q, k, v, window, causal, q_chunk, k_chunk)
    return out, (q, k, v, window, out, lse)


def _ca_bwd(causal, q_chunk, k_chunk, res, dout):
    q, k, v, window, out, lse = res
    B, S, KH, G, D = q.shape
    T = k.shape[1]
    cq = min(q_chunk, S)
    ck = min(k_chunk, T)
    nq, nk = S // cq, T // ck
    scale = D ** -0.5
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # (B,S,KH,G)
    qc = q.reshape(B, nq, cq, KH, G, D).transpose(1, 0, 2, 3, 4, 5)
    doc = dout.reshape(B, nq, cq, KH, G, D).transpose(1, 0, 2, 3, 4, 5)
    lsec = lse.reshape(B, KH, G, nq, cq).transpose(3, 0, 1, 2, 4)
    delc = delta.reshape(B, nq, cq, KH, G).transpose(1, 0, 3, 4, 2)
    kc = k.reshape(B, nk, ck, KH, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, ck, KH, D).transpose(1, 0, 2, 3, 4)

    def q_step(carry, inp):
        dk_acc, dv_acc = carry
        qi, doi, lsei, deli, q0 = inp

        def kv_step(carry2, inp2):
            dq_i, dk_a, dv_a = carry2
            ki, vi, k0 = inp2
            s = jnp.einsum("bqkgd,bckd->bkgqc", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            msk = _chunk_mask(q0, k0, cq, ck, S, T, causal, window)
            s = jnp.where(msk[None, None, None], s, -1e30)
            p = jnp.exp(s - lsei[..., None])                  # (B,KH,G,cq,ck)
            dv_c = jnp.einsum("bkgqc,bqkgd->bckd", p.astype(dout.dtype), doi,
                              preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqkgd,bckd->bkgqc", doi, vi,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - deli[..., None]) * scale           # (B,KH,G,cq,ck)
            dq_c = jnp.einsum("bkgqc,bckd->bqkgd", ds.astype(q.dtype), ki,
                              preferred_element_type=jnp.float32)
            dk_c = jnp.einsum("bkgqc,bqkgd->bckd", ds.astype(q.dtype), qi,
                              preferred_element_type=jnp.float32)
            dq_i = dq_i + dq_c
            dk_a = jax.lax.dynamic_update_slice(
                dk_a, (jax.lax.dynamic_slice(
                    dk_a, (0, k0, 0, 0), (B, ck, KH, D)) + dk_c),
                (0, k0, 0, 0))
            dv_a = jax.lax.dynamic_update_slice(
                dv_a, (jax.lax.dynamic_slice(
                    dv_a, (0, k0, 0, 0), (B, ck, KH, D)) + dv_c),
                (0, k0, 0, 0))
            return (dq_i, dk_a, dv_a), None

        dq0 = jnp.zeros((B, cq, KH, G, D), jnp.float32)
        k0s = jnp.arange(nk) * ck
        (dq_i, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_step, (dq0, dk_acc, dv_acc), (kc, vc, k0s))
        return (dk_acc, dv_acc), dq_i

    dk0 = jnp.zeros((B, T, KH, D), jnp.float32)
    dv0 = jnp.zeros((B, T, KH, D), jnp.float32)
    q0s = jnp.arange(nq) * cq
    (dk, dv), dqs = jax.lax.scan(
        q_step, (dk0, dv0), (qc, doc, lsec, delc, q0s))
    dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KH, G, D)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros_like(window))


_chunked_attention.defvjp(_ca_fwd, _ca_bwd)
