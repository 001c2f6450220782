"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers and compiles for a v5e chip that is
described, not attached (``jax.experimental.topologies``), which is where
the chip's compiler refuses block shapes, scratch buffers and primitives
that interpret mode accepts. Every test asserts that the kernel is in the
compiled program as a ``tpu_custom_call``. The topology is described inside
a fixture, never at import, and the tests skip only where it cannot be.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas

# qwen3-32b attention widths
H, KH, D = 64, 8, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_decode_attention_compiles_at_qwen3_32b_decode_widths(one_chip):
    B, T = 8, 4096
    hlo = _compiled_text(
        lambda q, k, v, n: decode_attention_pallas(q, k, v, n, 0), one_chip,
        ((B, H, D), jnp.bfloat16), ((B, T, KH, D), jnp.bfloat16),
        ((B, T, KH, D), jnp.bfloat16), ((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles_vmapped_over_serving_slots(one_chip):
    # the serving engine vmaps a batch-1 decode over its slots, each slot
    # with its own cache length: 8 slots x 2048 cached positions
    slots, T = 8, 2048
    step = jax.vmap(lambda q, k, v, n: decode_attention_pallas(q, k, v, n, 0))
    hlo = _compiled_text(
        step, one_chip, ((slots, 1, H, D), jnp.bfloat16),
        ((slots, 1, T, KH, D), jnp.bfloat16),
        ((slots, 1, T, KH, D), jnp.bfloat16), ((slots,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("seq", [512, 2048])
def test_flash_attention_compiles_at_qwen3_32b_prefill_widths(one_chip, seq):
    hlo = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v), one_chip,
        ((1, H, seq, D), jnp.bfloat16), ((1, KH, seq, D), jnp.bfloat16),
        ((1, KH, seq, D), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def _objective_shapes(k, services, degree=2, n_feat=3, lead=()):
    """Operand shapes of ``ops.rask_objective`` for ``services`` paper-like
    services (3 decision parameters, 3 SLOs and one ``n_feat``-feature
    polynomial relation each), optionally batched over ``lead`` hosts."""
    dim = q = 3 * services
    r = services
    terms = {1: 4, 2: 10, 3: 20}[degree]
    f32, i32 = jnp.float32, jnp.int32
    return [(lead + (k, dim), f32), ((r, n_feat), i32), ((r, terms), f32),
            ((r, terms, n_feat), i32), ((r, terms), f32), ((r, n_feat), f32),
            ((q,), i32), ((q,), i32), ((q,), f32), ((q,), f32), ((q,), i32),
            ((q,), i32), ((services,), f32)]


def test_rask_objective_compiles_at_9_services(one_chip):
    services = 9
    fn = partial(ops.rask_objective, n_services=services, max_degree=2,
                 impl="pallas")
    hlo = _compiled_text(fn, one_chip, *_objective_shapes(6, services))
    assert "tpu_custom_call" in hlo


def test_rask_objective_compiles_vmapped_over_a_fleet_bucket(one_chip):
    # one layout bucket of the 1000-service fleet solve: 25 hosts of 10
    # services (a quarter of the 100 hosts, one shard of four), 6 starts
    services = 10
    fn = jax.vmap(lambda a, *t: ops.rask_objective(
        a, *t, n_services=services, max_degree=2, impl="pallas"),
        in_axes=(0,) + (None,) * 12)
    hlo = _compiled_text(fn, one_chip,
                         *_objective_shapes(6, services, lead=(25,)))
    assert "tpu_custom_call" in hlo
