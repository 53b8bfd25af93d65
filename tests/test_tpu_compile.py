"""Compile rehearsals for a TPU v5e: the serving path's kernels at the
paper's widths (d=768, h=4096, k=32), compiled by the TPU compiler for a
described chip that need not be attached.  Nothing runs, so this proves
lowering, tiling and VMEM fit — not results or speed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU compiler library at a time, and every xdist
worker imports this file.  The kernels' ``interpret`` switch reads the
default backend (the CPU here), so the tests point it at the chip.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.compat import P
from repro.core.types import SparseCodes
from repro.kernels.fused_encode import ops as encode_ops
from repro.kernels.sparse_dot import ops

D, H, K = 768, 4096, 32          # the paper's SAE widths
N, Q, TOP_N = 16384, 64, 100     # catalog slice, query panel, registry.TOP_N
B = 2048                         # two-stage per-query candidate budget


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def on_chip(topo, monkeypatch):
    """A ShapeDtypeStruct factory on one described chip, with the kernel
    wrappers steered off interpret mode."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(encode_ops, "_on_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("batch", [Q, 1024])
def test_fused_encode_compiles(on_chip, batch):
    _compile(encode_ops.fused_encode, on_chip((batch, D), jnp.float32),
             on_chip((D, H), jnp.float32), on_chip((H,), jnp.float32), K)


def _candidates(S, quantized, gathered=False):
    lead = (Q, B) if gathered else (N,)
    if quantized:
        return (S(lead + (K,), jnp.int8), S(lead + (K,), jnp.int16),
                S(lead, jnp.float32), S(lead, jnp.float32))
    return (S(lead + (K,), jnp.float32), S(lead + (K,), jnp.int32),
            S(lead, jnp.float32))


def _query_codes(S):
    return S((Q, K), jnp.float32), S((Q, K), jnp.int32)


def test_sparse_dot_compiles(on_chip):
    S = on_chip
    _compile(ops.sparse_dot, S((N, K), jnp.float32), S((N, K), jnp.int32),
             S((Q, H), jnp.float32))


# (wrapper, quantized candidates, sparse query codes) — every generation
# select_retrieve_fn can return on the kernel path
_SHARED = [
    (ops.fused_retrieve, False, False),
    (ops.fused_retrieve_sparse_q, False, True),
    (ops.fused_retrieve_quantized, True, False),
    (ops.fused_retrieve_quantized_sparse_q, True, True),
    (ops.fused_retrieve_quantized_mxu, True, False),
    (ops.fused_retrieve_quantized_mxu_sparse_q, True, True),
]


@pytest.mark.parametrize("fn,quantized,sparse_q", _SHARED,
                         ids=lambda v: getattr(v, "__name__", str(v)))
def test_retrieve_generation_compiles(on_chip, fn, quantized, sparse_q):
    S = on_chip
    args = _candidates(S, quantized)
    if sparse_q:
        _compile(fn, *args, *_query_codes(S), H, n=TOP_N, interpret=False)
    else:
        _compile(fn, *args, S((Q, H), jnp.float32), n=TOP_N,
                 interpret=False)


@pytest.mark.parametrize("fn,quantized", [
    (ops.fused_retrieve_sparse_q, False),
    (ops.fused_retrieve_quantized_sparse_q, True),
    (ops.fused_retrieve_quantized_mxu_sparse_q, True),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_segmented_retrieve_compiles(on_chip, fn, quantized):
    """The deletion-mask (``alive``) variants a segmented index serves."""
    S = on_chip
    _compile(fn, *_candidates(S, quantized), *_query_codes(S), H, n=TOP_N,
             interpret=False, alive=S((N,), jnp.float32))


@pytest.mark.parametrize("fn,quantized", [
    (ops.fused_retrieve_sparse_q, False),
    (ops.fused_retrieve_quantized_sparse_q, True),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_merge_counter_variants_compile(on_chip, fn, quantized):
    """The served generations with the scan's merge counters output."""
    S = on_chip
    _compile(fn, *_candidates(S, quantized), *_query_codes(S), H, n=TOP_N,
             interpret=False, with_stats=True)


@pytest.mark.parametrize("fn,quantized", [
    (ops.fused_retrieve_gathered_sparse_q, False),
    (ops.fused_retrieve_gathered_quantized_sparse_q, True),
    (ops.fused_retrieve_gathered_quantized_mxu_sparse_q, True),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_gathered_generation_compiles(on_chip, fn, quantized):
    S = on_chip
    _compile(fn, *_candidates(S, quantized, gathered=True),
             *_query_codes(S), H, n=TOP_N, interpret=False)


def test_sharded_retrieve_compiles_for_four_chips(topo, monkeypatch):
    """The candidate-sharded engine's whole-request program over a 4-way
    ``cand`` mesh of described chips: fused_encode on every chip, the
    per-shard retrieve kernels and the all-gather merge."""
    from jax.sharding import Mesh

    from repro.core import SAEConfig, build_index, init_params
    from repro.serving import EngineConfig, RetrievalEngine

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(encode_ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(4), ("cand",))
    rng = np.random.default_rng(0)
    codes = SparseCodes(
        jnp.asarray(rng.standard_normal((4 * N, K), np.float32)),
        jnp.asarray(rng.integers(0, H, (4 * N, K), np.int32)), H)
    engine = RetrievalEngine(
        build_index(codes), init_params(SAEConfig(), jax.random.PRNGKey(0)),
        config=EngineConfig(use_kernel=True, mesh=mesh))
    arrays, _ = engine._serve_leaves()
    placed = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sh) for v, sh
              in zip(arrays, engine._serve_shardings(arrays))]
    xb = jax.ShapeDtypeStruct((Q, D), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    compiled = _compile(engine._serve_fn(TOP_N), placed, xb)
    assert "all-gather" in compiled.as_text()
