"""Compile every TPU-route Pallas kernel for a described TPU v5e.

Interpret mode (the CPU suite) cannot see what Mosaic refuses: casts it does
not lower, 8-bit vector math, lane-splitting reshapes, unaligned blocks.
These tests compile each kernel the TPU backend routes through
(``interpret=False``) for one chip of a v5e topology that is described, not
attached, at the widths ``mamba2-130m`` trains with: its whole bucketed
parameter buffer, ternary blocks of 1024 lanes, and 4 stacked worker rows, so
every decode grid has many tiles and several workers.  Only shapes are
passed; nothing runs.  Each compiled kernel keeps its own name, the one a
profiler trace shows; the jaxpr of every kernel, the interpret-only ones
too, carries that name.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dense import dense_copy, dense_decode_sum, dense_decode_sum_mean
from repro.kernels.nat_pack import (
    LANES, nat_decode_sum, nat_decode_sum_apply, nat_decode_sum_mean, nat_pack,
    nat_pack_prng,
)
from repro.kernels.quantize_pack import quantize_pack, quantize_pack_prng
from repro.kernels.sparse import (
    sparse_decode_sum, sparse_decode_sum_mean, sparse_gather,
)
from repro.kernels.unpack_reduce import (
    unpack_reduce, unpack_reduce_apply, unpack_reduce_mean,
)

N_WORKERS = 4
BLOCK = 1024          # mamba2-130m's ternary block (ModelConfig.comp_block)
ALPHA = 0.25


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def flat_size():
    """Padded length of mamba2-130m's bucketed DIANA buffer (block 1024)."""
    from repro.configs import get_config
    from repro.core import CompressionConfig
    from repro.core.diana import bucket_layout
    from repro.models import init_model

    cfg = get_config("mamba2-130m")
    params = jax.eval_shape(lambda k: init_model(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    comp = CompressionConfig(method="diana", block_size=BLOCK)
    return bucket_layout(comp, params).padded_size


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _ternary(d):
    m = d // BLOCK
    return {
        "quantize_pack": (
            functools.partial(quantize_pack, p=float("inf"), interpret=False),
            [((m, BLOCK), jnp.float32), ((m, BLOCK), jnp.uint32)]),
        "quantize_pack_prng": (
            functools.partial(quantize_pack_prng, p=float("inf")),
            [((m, BLOCK), jnp.float32), ((2,), jnp.int32)]),
        "unpack_reduce": (
            functools.partial(unpack_reduce, interpret=False),
            [((N_WORKERS, m, BLOCK // 4), jnp.uint8),
             ((N_WORKERS, m, 1), jnp.float32)]),
        "unpack_reduce_mean": (
            functools.partial(unpack_reduce_mean, interpret=False),
            [((N_WORKERS, m, BLOCK // 4), jnp.uint8),
             ((N_WORKERS, m, 1), jnp.float32)]),
        "unpack_reduce_apply": (
            functools.partial(unpack_reduce_apply, alpha=ALPHA,
                              interpret=False),
            [((N_WORKERS, m, BLOCK // 4), jnp.uint8),
             ((N_WORKERS, m, 1), jnp.float32), ((d,), jnp.float32)]),
    }


def _natural(d):
    return {
        "nat_pack": (
            functools.partial(nat_pack, interpret=False),
            [((d,), jnp.float32), ((d,), jnp.uint32)]),
        "nat_pack_prng": (
            nat_pack_prng, [((d,), jnp.float32), ((2,), jnp.int32)]),
        "nat_decode_sum": (
            functools.partial(nat_decode_sum, interpret=False),
            [((N_WORKERS, d), jnp.int16)]),
        "nat_decode_sum_mean": (
            functools.partial(nat_decode_sum_mean, interpret=False),
            [((N_WORKERS, d), jnp.int16)]),
        "nat_decode_sum_apply": (
            functools.partial(nat_decode_sum_apply, alpha=ALPHA,
                              interpret=False),
            [((N_WORKERS, d), jnp.int16), ((d,), jnp.float32)]),
    }


KERNELS = [*_ternary(BLOCK), *_natural(BLOCK)]


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, one_chip, flat_size):
    """Each kernel compiles, and its Mosaic call carries its own stable name:
    the instruction the profiler's trace shows is ``%<name>.<n>``, and the
    name sits in the call's op_name, where only an explicit ``name=`` on the
    ``pallas_call`` puts it."""
    fn, shapes = {**_ternary(flat_size), **_natural(flat_size)}[name]
    text = _compiled_text(fn, one_chip, *shapes)
    calls = [l for l in text.splitlines() if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 1
    assert re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", calls[0]), calls[0][:120]
    assert f"/{name}/pallas_call" in calls[0]


def _pallas_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _pallas_eqns(inner)


_S = jax.ShapeDtypeStruct
_TERNARY_PAYLOAD = (_S((3, 16, 32), jnp.uint8), _S((3, 16, 1), jnp.float32))
_NAT_CODES = (_S((3, 16 * LANES), jnp.int16),)
DECODES = {
    "unpack_reduce": (unpack_reduce, _TERNARY_PAYLOAD),
    "unpack_reduce_mean": (unpack_reduce_mean, _TERNARY_PAYLOAD),
    "unpack_reduce_apply": (
        functools.partial(unpack_reduce_apply, alpha=ALPHA),
        (*_TERNARY_PAYLOAD, _S((16 * 128,), jnp.float32))),
    "nat_decode_sum": (nat_decode_sum, _NAT_CODES),
    "nat_decode_sum_mean": (nat_decode_sum_mean, _NAT_CODES),
    "nat_decode_sum_apply": (
        functools.partial(nat_decode_sum_apply, alpha=ALPHA),
        (*_NAT_CODES, _S((16 * LANES,), jnp.float32))),
}


@pytest.mark.parametrize("name", DECODES)
def test_decode_grid_reduces_over_workers_last(name):
    """The worker axis is the innermost grid axis and declared arbitrary, so
    compiled Pallas keeps each output tile resident while it sums workers
    (an outer worker axis would write a tile back before the next worker
    adds to it, and never reload it)."""
    kernel, args = DECODES[name]
    jaxpr = jax.make_jaxpr(functools.partial(kernel, tile_m=8))(*args)
    (eqn,) = _pallas_eqns(jaxpr.jaxpr)
    assert eqn.params["grid_mapping"].grid == (2, 3)     # (m_tiles, workers)
    params = eqn.params["compiler_params"]["mosaic_tpu"]
    assert params.dimension_semantics == ("parallel", "arbitrary")


_IDX = _S((3, 16), jnp.int32)
_NAMED = {
    **DECODES,
    "quantize_pack": (quantize_pack, (_S((16, 128), jnp.float32),
                                      _S((16, 128), jnp.uint32))),
    "nat_pack": (nat_pack, (_S((16 * LANES,), jnp.float32),
                            _S((16 * LANES,), jnp.uint32))),
    "dense_copy": (dense_copy, (_S((256,), jnp.float32),)),
    "dense_decode_sum": (dense_decode_sum, (_S((3, 256), jnp.float32),)),
    "dense_decode_sum_mean": (dense_decode_sum_mean, (_S((3, 256), jnp.float32),)),
    "sparse_gather": (sparse_gather, (_S((256,), jnp.float32), _S((16,), jnp.int32))),
    "sparse_decode_sum": (
        functools.partial(sparse_decode_sum, d=256),
        (_IDX, _S((3, 16), jnp.float32), _S((16,), jnp.float32))),
    "sparse_decode_sum_mean": (
        functools.partial(sparse_decode_sum_mean, d=256),
        (_IDX, _S((3, 16), jnp.float32), _S((16,), jnp.float32))),
}


@pytest.mark.parametrize("name", sorted(_NAMED))
def test_pallas_call_has_its_own_name(name):
    """Every ``pallas_call`` in ``repro.kernels`` names itself after its
    wrapper (the in-kernel-PRNG encodes, which only lower for the chip, are
    checked in their TPU lowering above), so no two kernels share a name in
    a trace.  This covers the interpret-only dense and sparse kernels too."""
    kernel, args = _NAMED[name]
    (eqn,) = _pallas_eqns(jax.make_jaxpr(kernel)(*args).jaxpr)
    assert eqn.params["name"] == name


def test_mamba_layer_compiles_with_ssd_kernels(one_chip, monkeypatch):
    """One whole mamba2-130m layer under ``jax.checkpoint``, forward and
    backward, at batch 1 and seq 4096, as the model routes it on a TPU: the
    compiled program holds the two SSD kernels and none of the XLA scan's
    per-head (Q, Q) f32 buffers or head-repeated (…, 24, 128) B/C buffers."""
    import repro.models.mamba2 as M
    from repro.configs import get_config

    cfg = get_config("mamba2-130m")
    params = jax.eval_shape(lambda k: M.init_mamba(k, cfg, jnp.bfloat16),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1, 4096, cfg.d_model), jnp.bfloat16, sharding=one_chip)
    layer = jax.checkpoint(lambda p, x: M.mamba_layer(p, x, cfg)[0])

    def loss(p, x):
        return jnp.sum(layer(p, x).astype(jnp.float32))

    with monkeypatch.context() as m:   # the model asks the backend; trace as on a TPU
        m.setattr(jax, "default_backend", lambda: "tpu")
        traced = jax.jit(jax.grad(loss, argnums=(0, 1))).trace(params, x)
    text = traced.lower().compile().as_text()
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(set(calls)) == ["ssd_chunk_scan", "ssd_chunk_scan_bwd"], calls
    assert not re.search(r"f32\[[\d,]*256,256\]", text)
    assert not re.search(r"\[[\d,]*24,128\]", text)
