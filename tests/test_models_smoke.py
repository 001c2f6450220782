"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
output shapes + no NaNs (assignment requirement f)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS
from repro.models import build


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def _batch(cfg, key, B=2, S=32):
    if cfg.family == "encdec":
        return {"frames": jax.random.normal(key, (B, S, cfg.d_model),
                                            jnp.float32),
                "tokens": jnp.ones((B, 8), jnp.int32),
                "labels": jnp.ones((B, 8), jnp.int32)}
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "labels": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_train_step(arch, key):
    cfg = ARCHS[arch].smoke()
    model = build(cfg)
    params = model.init(key)
    batch = _batch(cfg, key)
    loss, metrics = model.loss(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch
    # one gradient step
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert gnorm > 0 and jnp.isfinite(gnorm), arch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_serve_path(arch, key):
    cfg = ARCHS[arch].smoke()
    model = build(cfg)
    params = model.init(key)
    B, S = 2, 32
    batch = _batch(cfg, key, B, S)
    batch.pop("labels")
    logits, cache = model.prefill(params, batch, max_seq=S + 8)
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), arch
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(3):
        logits, cache = model.decode(params, tok, cache)
        assert logits.shape == (B, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits))), arch
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)


def _forward_logits(cfg, params, toks):
    from repro.models import transformer as T
    forward = {"ssm": T.ssm_forward, "hybrid": T.hybrid_forward}.get(
        cfg.family, T.decoder_forward)
    return forward(params, cfg, toks)[0]


@pytest.mark.parametrize("arch", ["qwen3-32b", "mamba2-370m",
                                  "jamba-1.5-large-398b"])
def test_decode_matches_teacher_forcing(arch, key):
    """prefill(t[:k]) + decode(t[k]) logits == forward(t[:k+1]) last logits."""
    cfg = dataclasses.replace(ARCHS[arch].smoke(), dtype="float32")
    model = build(cfg)
    params = model.init(key)
    B, S = 2, 16
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab, jnp.int32)
    # teacher-forced logits at position S (prediction after S+1 tokens)
    full = _forward_logits(cfg, params, toks)
    want = full[:, S - 1]   # prediction for token at index S
    logits, cache = model.prefill(params, {"tokens": toks[:, :S]},
                                  max_seq=S + 4)
    got = logits
    assert jnp.allclose(got, want, atol=2e-3, rtol=1e-3), arch
    # one decode step must match teacher forcing at the next position
    want2 = full[:, S]
    got2, _ = model.decode(params, toks[:, S:S + 1], cache)
    assert jnp.allclose(got2, want2, atol=5e-3, rtol=1e-2), (
        arch, float(jnp.max(jnp.abs(got2 - want2))))


@pytest.mark.parametrize("arch,mixed", [
    ("qwen3-32b", False), ("gemma3-1b", False), ("gemma3-1b", True),
    ("mamba2-370m", False)])
def test_decode_from_empty_cache_matches_teacher_forcing(arch, mixed, key):
    """Token by token from an empty cache to its last row (gemma3's ring
    caches wrap): each step reads the rows the earlier steps wrote, so its
    logits match teacher forcing at that position. (MoE families drop
    tokens past an expert's capacity in the forward pass, so their logits
    differ from decode's on their own.)"""
    cfg = dataclasses.replace(ARCHS[arch].smoke(), dtype="float32",
                              mixed_cache=mixed)
    model = build(cfg)
    params = model.init(key)
    B, S = 2, 20
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab, jnp.int32)
    full = _forward_logits(cfg, params, toks)
    decode = jax.jit(model.decode)
    cache = model.init_cache(B, S)
    for i in range(S):
        got, cache = decode(params, toks[:, i:i + 1], cache)
        assert jnp.allclose(got, full[:, i], atol=5e-3, rtol=1e-2), (
            arch, i, float(jnp.max(jnp.abs(got - full[:, i]))))
