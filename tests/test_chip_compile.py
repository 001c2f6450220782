"""What the compiler makes of the main path.

The Pallas kernels compile for a TPU v5e at real widths. Nothing runs: each
such test lowers and compiles for a v5e chip that is described, not
attached (``jax.experimental.topologies``), which is where the chip's
compiler refuses block shapes, scratch buffers and primitives that
interpret mode accepts, and asserts that the kernel is in the compiled
program as a ``tpu_custom_call``. The topology is described inside a
fixture, never at import, and the tests skip only where it cannot be.

The serving engine's decode step moves no more of its donated KV cache than
it must: the cache aliases through the program, each layer's slice is read
where it lies, and the one write puts the new rows in place. Checked on the
CPU's program and on the v5e's.
"""
import dataclasses
import math
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.kernels import ops
from repro.launch.hlo_cost import parse_module
from repro.models import build
from repro.serve.engine import EngineConfig, ServingEngine
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
        ((B, H, D), jnp.bfloat16), ((B, KH, T, D), jnp.bfloat16),
        ((B, KH, T, D), jnp.bfloat16), ((), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_decode_attention_compiles_vmapped_over_serving_slots(one_chip):
    # the serving engine vmaps a batch-1 decode over its slots, each slot
    # with its own cache length: 8 slots x 2048 cached positions
    slots, T = 8, 2048
    step = jax.vmap(lambda q, k, v, n: decode_attention_pallas(q, k, v, n, 0))
    hlo = _compiled_text(
        step, one_chip, ((slots, 1, H, D), jnp.bfloat16),
        ((slots, 1, KH, T, D), jnp.bfloat16),
        ((slots, 1, KH, T, D), jnp.bfloat16), ((slots,), jnp.int32))
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


# -- the serving engine's decode step and its KV cache -------------------------

_MOVES = {"copy", "copy-start", "transpose", "dynamic-slice",
          "dynamic-update-slice", "scatter", "gather", "slice", "pad",
          "concatenate"}
_WRITES = {"dynamic-update-slice", "scatter"}
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _shapes(type_text: str):
    return [[int(d) for d in dims.split(",") if d]
            for dims in re.findall(r"[a-z0-9]+\[([0-9,]*)\]", type_text)]


def _step_program(cfg, slots, max_seq, sharding=None):
    """The compiled decode step of a ``ServingEngine`` over ``cfg`` (weights
    as shapes only), and its K cache leaf."""
    model = build(cfg)
    engine = ServingEngine(model, None, EngineConfig(slots=slots,
                                                     max_seq=max_seq))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        (params, engine._cache, engine._last))
    return engine._step.lower(*args).compile().as_text(), engine._cache["k"]


def _cache_route(hlo: str, leaf):
    """The step's scheduled ops (those outside fusion bodies) that move a
    layer slice of a KV cache leaf or more, sorted into ``writes`` (new rows
    into a whole leaf), ``reads`` (a layer slice taken out of a whole leaf)
    and ``copies`` (the rest); and whether every K and V parameter of the
    program is aliased to an output. Cache data is told from weights by the
    sequence length, a size no weight has."""
    whole = leaf.size
    layer = whole // leaf.shape[1]           # (slots, L, 1, KH, S, D)
    seq = leaf.shape[4]

    def cache_elems(type_text: str) -> int:
        """Elements of a cache-shaped value of a layer slice or more, else 0."""
        shapes = _shapes(type_text)
        n = sum(math.prod(d) for d in shapes)
        return n if n >= layer and any(seq in d for d in shapes) else 0

    comps, entry = parse_module(hlo)
    fused = {m.group(1) for c in comps.values() for i in c.instrs
             if i.op == "fusion" for m in [_CALLS.search(i.attrs)] if m}

    def writes_rows(ins):
        if ins.op == "fusion":
            body = comps[_CALLS.search(ins.attrs).group(1)]
            return any(i.op in _WRITES for i in body.instrs)
        return ins.op in _WRITES

    route = {"writes": [], "reads": [], "copies": []}
    for name, comp in comps.items():
        if name in fused:
            continue
        for ins in comp.instrs:
            if ins.op not in _MOVES | {"fusion"}:
                continue
            out = cache_elems(ins.result)
            big = [n for n in (cache_elems(comp.symtab.get(o, ""))
                               for o in ins.operands) if n]
            if not out and not (ins.op in _MOVES and big):
                continue                     # small, or a fused consumer
            if out == whole and big == [whole] and writes_rows(ins):
                kind = "writes"
            elif out < whole and set(big) == {whole}:
                kind = "reads"
            else:
                kind = "copies"
            route[kind].append(f"{ins.op} {ins.name} {ins.result}")
    params = [int(i.operands[0]) for i in comps[entry].instrs
              if i.op == "parameter" and cache_elems(i.result) == whole]
    aliased = {int(n) for n in re.findall(
        r"\{[0-9, ]*\}: \((\d+), \{[0-9, ]*\}, (?:may|must)-alias\)",
        hlo)}
    return route, len(params) == 2 and set(params) <= aliased


def test_decode_step_writes_only_new_rows_into_the_donated_cache():
    # float32: the CPU computes bfloat16 scatters in float32, by converting
    # the whole cache around them
    cfg = dataclasses.replace(get("qwen3-32b").smoke(), n_layers=4)
    hlo, leaf = _step_program(cfg, slots=6, max_seq=384)
    route, aliased = _cache_route(hlo, leaf)
    assert aliased
    assert route["copies"] == []
    assert len(route["writes"]) == 2, route["writes"]
    # XLA-CPU hands its dots whole operands: at most one read of K's and one
    # of V's layer slice per layer (the chip's program has none, below)
    assert len(route["reads"]) <= 2, route["reads"]


def test_decode_step_reads_the_cache_in_place_on_the_chip(one_chip):
    # the attention widths of qwen3-32b, a small rest
    cfg = dataclasses.replace(get("qwen3-32b"), n_layers=4, d_model=1024,
                              d_ff=2048, vocab=4096, dtype="bfloat16")
    hlo, leaf = _step_program(cfg, slots=8, max_seq=512, sharding=one_chip)
    route, aliased = _cache_route(hlo, leaf)
    assert aliased
    assert route["copies"] == [] and route["reads"] == [], route
    assert len(route["writes"]) == 2, route["writes"]
