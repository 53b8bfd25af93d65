"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode on CPU).

Sweeps shapes/dtypes per the deliverable spec; hypothesis property tests on
the invariants live in test_properties.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core.quantized_codes import dequantize_codes, quantize_codes
from repro.core.sae import normalize_input
from repro.core.types import SparseCodes
from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.fused_encode.ops import fused_encode
from repro.kernels.fused_encode.ref import fused_encode_ref
from repro.kernels.sparse_dot import kernel as sparse_dot_kernel
from repro.kernels.sparse_dot.ops import (
    fused_retrieve,
    fused_retrieve_gathered_sparse_q,
    fused_retrieve_quantized,
    fused_retrieve_quantized_mxu,
    fused_retrieve_quantized_mxu_sparse_q,
    fused_retrieve_quantized_sparse_q,
    fused_retrieve_sparse_q,
    sparse_dot,
)
from repro.kernels.sparse_dot.ref import (
    _quantize_panel,
    retrieve_quantized_mxu_ref,
    retrieve_quantized_mxu_sparse_q_ref,
    retrieve_quantized_ref,
    retrieve_quantized_sparse_q_ref,
    retrieve_ref,
    retrieve_sparse_q_ref,
    sparse_dot_ref,
)
from repro.kernels.topk_mask.ops import topk_mask
from repro.kernels.topk_mask.ref import topk_mask_ref


# ----------------------------------------------------------------- sparse_dot
@pytest.mark.parametrize("n", [64, 256, 1000, 4097])
@pytest.mark.parametrize("k,h", [(8, 256), (32, 4096)])
def test_sparse_dot_shapes(n, k, h):
    key = jax.random.PRNGKey(n * k)
    k1, k2, k3 = jax.random.split(key, 3)
    vals = jax.random.normal(k1, (n, k), jnp.float32)
    idx = jax.random.randint(k2, (n, k), 0, h, dtype=jnp.int32)
    q = jax.random.normal(k3, (2, h), jnp.float32)
    np.testing.assert_allclose(
        sparse_dot(vals, idx, q), sparse_dot_ref(vals, idx, q), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16])
def test_sparse_dot_dtypes(qdtype):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    vals = jax.random.normal(k1, (128, 16), jnp.float32)
    idx = jax.random.randint(k2, (128, 16), 0, 512, dtype=jnp.int32)
    q = jax.random.normal(k3, (1, 512)).astype(qdtype)
    got = sparse_dot(vals, idx, q)
    want = sparse_dot_ref(vals, idx, q)
    rtol = 1e-5 if qdtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=rtol, atol=rtol
    )


def test_sparse_dot_duplicate_indices_sum():
    # duplicate column indices in one row must contribute additively
    vals = jnp.array([[1.0, 2.0, 3.0]])
    idx = jnp.array([[5, 5, 7]], dtype=jnp.int32)
    q = jnp.zeros((1, 16)).at[0, 5].set(10.0).at[0, 7].set(1.0)
    np.testing.assert_allclose(sparse_dot(vals, idx, q), [[33.0]], rtol=1e-6)


def test_sparse_dot_ragged_query_panel():
    # Q not a multiple of BLOCK_Q exercises the query-padding path of the
    # blocked multi-query kernel.
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    vals = jax.random.normal(k1, (300, 8), jnp.float32)
    idx = jax.random.randint(k2, (300, 8), 0, 128, dtype=jnp.int32)
    q = jax.random.normal(k3, (13, 128), jnp.float32)
    np.testing.assert_allclose(
        sparse_dot(vals, idx, q), sparse_dot_ref(vals, idx, q), rtol=1e-5, atol=1e-5
    )


# ------------------------------------------------------------- fused_retrieve
def _retrieve_case(n, q, k, h, seed):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    vals = jax.random.normal(k1, (n, k), jnp.float32)
    idx = jax.random.randint(k2, (n, k), 0, h, dtype=jnp.int32)
    qq = jax.random.normal(k3, (q, h), jnp.float32)
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(vals, axis=-1), 1e-8)
    return vals, idx, qq, inv


# ragged N (pads candidate tiles) and ragged Q (pads the query panel)
@pytest.mark.parametrize("n,q,topn", [(64, 9, 64), (256, 1, 5), (1000, 3, 10), (4097, 5, 20)])
def test_fused_retrieve_matches_bruteforce(n, q, topn):
    vals, idx, qq, inv = _retrieve_case(n, q, 8, 256, seed=n + q)
    want_v, want_i = jax.lax.top_k(sparse_dot_ref(vals, idx, qq) * inv[None], topn)
    got_v, got_i = fused_retrieve(vals, idx, inv, qq, n=topn)
    np.testing.assert_allclose(got_v, want_v, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got_i, want_i)
    ref_v, ref_i = retrieve_ref(vals, idx, inv, qq, n=topn, block_n=300)
    np.testing.assert_allclose(ref_v, want_v, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(ref_i, want_i)


def test_fused_retrieve_tied_scores_match_lax_topk():
    # Duplicated candidate rows give exactly-tied scores across tile
    # boundaries; both the streaming kernel epilogue and the chunked jnp
    # reference must resolve them like lax.top_k (lowest candidate id wins).
    base_v, base_i, qq, _ = _retrieve_case(40, 3, 4, 64, seed=7)
    vals = jnp.tile(base_v, (8, 1))
    idx = jnp.tile(base_i, (8, 1))
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(vals, axis=-1), 1e-8)
    want_v, want_i = jax.lax.top_k(sparse_dot_ref(vals, idx, qq) * inv[None], 17)
    got_v, got_i = fused_retrieve(vals, idx, inv, qq, n=17, block_n=64, block_q=2)
    np.testing.assert_array_equal(got_i, want_i)
    ref_v, ref_i = retrieve_ref(vals, idx, inv, qq, n=17, block_n=96)
    np.testing.assert_array_equal(ref_i, want_i)


def test_fused_retrieve_single_query_and_n_equals_N():
    vals, idx, qq, inv = _retrieve_case(96, 1, 8, 128, seed=11)
    v, i = fused_retrieve(vals, idx, inv, qq[0], n=96)
    assert v.shape == (96,) and i.shape == (96,)
    # exhaustive n == N: every candidate id must surface exactly once
    assert sorted(np.asarray(i).tolist()) == list(range(96))
    with pytest.raises(ValueError):
        fused_retrieve(vals, idx, inv, qq, n=97)


def test_fused_retrieve_all_negative_scores_exclude_padding():
    # all-negative scores: padded rows (masked to -inf, not 0) must never
    # win even though 0 would outrank every real candidate
    vals = -jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (130, 4)))
    idx = jax.random.randint(jax.random.PRNGKey(1), (130, 4), 0, 64, dtype=jnp.int32)
    q = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (2, 64)))
    inv = jnp.ones((130,), jnp.float32)
    _, ids = fused_retrieve(vals, idx, inv, q, n=20)
    assert (np.asarray(ids) < 130).all()


# ---------------------------------------------------- fused_retrieve_sparse_q
def _sparse_q_case(n, q, kq, h, seed, idx_hi=None):
    """Candidate codes + SPARSE query codes.  ``idx_hi`` < h concentrates
    query indices to force duplicate indices within code rows."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    vals = jax.random.normal(ks[0], (n, kq), jnp.float32)
    idx = jax.random.randint(ks[1], (n, kq), 0, h, dtype=jnp.int32)
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(vals, axis=-1), 1e-8)
    qv = jax.random.normal(ks[2], (q, kq), jnp.float32)
    qi = jax.random.randint(ks[3], (q, kq), 0, idx_hi or h, dtype=jnp.int32)
    return vals, idx, inv, qv, qi


def _densify(qv, qi, h):
    def one(v, i):
        return jnp.zeros((h,), v.dtype).at[i].add(v)

    return jax.vmap(one)(qv, qi)


# ragged N (candidate-tile padding), ragged Q (query-panel padding), and
# Q > the ref path's q_chunk (exercises its chunked densify)
@pytest.mark.parametrize("n,q,topn", [(64, 9, 64), (256, 1, 5),
                                      (1000, 13, 10), (4097, 5, 20),
                                      (300, 150, 7)])
def test_sparse_q_bit_identical_to_densify_composed(n, q, topn):
    """The sparse-query generation (kernel AND ref) must be BIT-identical —
    scores, ids, ties — to densify + the dense-query path it replaces."""
    vals, idx, inv, qv, qi = _sparse_q_case(n, q, 8, 256, seed=n + q)
    qd = _densify(qv, qi, 256)
    want_v, want_i = fused_retrieve(vals, idx, inv, qd, n=topn)
    got_v, got_i = fused_retrieve_sparse_q(vals, idx, inv, qv, qi, 256, n=topn)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    rwant_v, rwant_i = retrieve_ref(vals, idx, inv, qd, n=topn)
    rgot_v, rgot_i = retrieve_sparse_q_ref(vals, idx, inv, qv, qi, 256, n=topn)
    np.testing.assert_array_equal(np.asarray(rgot_v), np.asarray(rwant_v))
    np.testing.assert_array_equal(np.asarray(rgot_i), np.asarray(rwant_i))


def test_sparse_q_tied_scores_match_lax_topk():
    # duplicated candidate rows -> exactly-tied scores across tile
    # boundaries; the sparse-query paths must resolve them like lax.top_k
    # (lowest candidate id wins), byte-for-byte with the dense-query paths
    base_v, base_i, _, qv, qi = _sparse_q_case(40, 3, 4, 64, seed=7)
    vals = jnp.tile(base_v, (8, 1))
    idx = jnp.tile(base_i, (8, 1))
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(vals, axis=-1), 1e-8)
    qd = _densify(qv, qi, 64)
    want_v, want_i = jax.lax.top_k(sparse_dot_ref(vals, idx, qd) * inv[None], 17)
    got_v, got_i = fused_retrieve_sparse_q(vals, idx, inv, qv, qi, 64, n=17,
                                           block_n=64, block_q=2)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(got_v, want_v, rtol=1e-6, atol=1e-7)
    ref_v, ref_i = retrieve_sparse_q_ref(vals, idx, inv, qv, qi, 64, n=17,
                                         block_n=96)
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(want_i))


def test_sparse_q_duplicate_indices_within_code_row():
    # duplicate indices inside one query code row must contribute
    # additively — and in the same accumulation order as sparse.densify's
    # scatter-add, so results stay bit-identical to the composed path
    vals = jnp.array([[1.0, 2.0], [3.0, 0.5], [0.25, 4.0]])
    idx = jnp.array([[5, 7], [5, 5], [7, 2]], dtype=jnp.int32)
    inv = jnp.ones((3,), jnp.float32)
    qv = jnp.array([[0.3, 0.7, 0.11]])          # all three hit column 5
    qi = jnp.array([[5, 5, 5]], dtype=jnp.int32)
    qd = _densify(qv, qi, 16)
    want_v, want_i = fused_retrieve(vals, idx, inv, qd, n=3)
    got_v, got_i = fused_retrieve_sparse_q(vals, idx, inv, qv, qi, 16, n=3)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    rv, ri = retrieve_sparse_q_ref(vals, idx, inv, qv, qi, 16, n=3)
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(want_i))
    # heavy random duplication across many rows
    vals, idx, inv, qv, qi = _sparse_q_case(200, 11, 6, 128, seed=3, idx_hi=9)
    qd = _densify(qv, qi, 128)
    want = fused_retrieve(vals, idx, inv, qd, n=9)
    got = fused_retrieve_sparse_q(vals, idx, inv, qv, qi, 128, n=9)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_sparse_q_single_query_and_validation():
    vals, idx, inv, qv, qi = _sparse_q_case(96, 1, 8, 128, seed=11)
    v, i = fused_retrieve_sparse_q(vals, idx, inv, qv[0], qi[0], 128, n=96)
    assert v.shape == (96,) and i.shape == (96,)
    assert sorted(np.asarray(i).tolist()) == list(range(96))
    with pytest.raises(ValueError):
        fused_retrieve_sparse_q(vals, idx, inv, qv, qi, 128, n=97)


# ------------------------------------------------ fused_retrieve_quantized
def _quantized_case(n, q, k, h, seed):
    """Quantized candidate codes + their dequantized fp32 oracle twin.

    The norms come from the DEQUANTIZED values (exactly what build_index
    does with quantize=True), so the quantized path and the
    dequantize-then-retrieve oracle score the same space.
    """
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    vals = jax.random.normal(ks[0], (n, k), jnp.float32)
    idx = jax.random.randint(ks[1], (n, k), 0, h, dtype=jnp.int32)
    qc = quantize_codes(SparseCodes(values=vals, indices=idx, dim=h))
    deq = dequantize_codes(qc)
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(deq.values, axis=-1), 1e-8)
    qq = jax.random.normal(ks[2], (q, h), jnp.float32)
    return qc, deq, inv, qq


# ragged N (candidate-tile padding) and ragged Q (query-panel padding)
@pytest.mark.parametrize("n,q,topn", [(64, 9, 64), (256, 1, 5),
                                      (1000, 3, 10), (4097, 5, 20)])
def test_quantized_bit_identical_to_dequantized(n, q, topn):
    """The quantized generation (kernel AND ref) must be BIT-identical —
    scores, ids, ties — to dequantize + the fp32 path it replaces."""
    qc, deq, inv, qq = _quantized_case(n, q, 8, 256, seed=n + q)
    want_v, want_i = fused_retrieve(deq.values, deq.indices, inv, qq, n=topn)
    got_v, got_i = fused_retrieve_quantized(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=topn
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    rwant_v, rwant_i = retrieve_ref(deq.values, deq.indices, inv, qq, n=topn)
    rgot_v, rgot_i = retrieve_quantized_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=topn
    )
    np.testing.assert_array_equal(np.asarray(rgot_v), np.asarray(rwant_v))
    np.testing.assert_array_equal(np.asarray(rgot_i), np.asarray(rwant_i))


def test_quantized_tied_scores_match_lax_topk():
    # duplicated candidate rows share one quantization scale, so their
    # dequantized scores tie EXACTLY across tile boundaries; the quantized
    # epilogue must resolve them like lax.top_k (lowest candidate id wins)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    vals = jnp.tile(jax.random.normal(ks[0], (40, 4), jnp.float32), (8, 1))
    idx = jnp.tile(jax.random.randint(ks[1], (40, 4), 0, 64, jnp.int32), (8, 1))
    qq = jax.random.normal(ks[2], (3, 64), jnp.float32)
    qc = quantize_codes(SparseCodes(values=vals, indices=idx, dim=64))
    deq = dequantize_codes(qc)
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(deq.values, axis=-1), 1e-8)
    want_v, want_i = jax.lax.top_k(
        sparse_dot_ref(deq.values, deq.indices, qq) * inv[None], 17
    )
    got_v, got_i = fused_retrieve_quantized(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=17,
        block_n=64, block_q=2,
    )
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_allclose(got_v, want_v, rtol=1e-6, atol=1e-7)
    ref_v, ref_i = retrieve_quantized_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=17, block_n=96
    )
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(want_i))


@pytest.mark.parametrize("h,want_dtype", [(256, jnp.int16),
                                          (40000, jnp.int16),
                                          (70000, jnp.int32)])
def test_quantized_index_dtype_and_wraparound(h, want_dtype):
    """int16 indices cover all of h < 65536 via the low-16-bit widen
    (h=40000 puts indices in the two's-complement wrap region); h >= 65536
    falls back to int32.  All must stay bit-identical to the fp32 path."""
    qc, deq, inv, qq = _quantized_case(300, 2, 8, h, seed=h)
    assert qc.indices.dtype == want_dtype
    want_v, want_i = fused_retrieve(deq.values, deq.indices, inv, qq, n=7)
    got_v, got_i = fused_retrieve_quantized(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=7
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    # the ref pair has its own (jnp-sum) accumulation order: bit-identity
    # holds quantized-vs-dequantized WITHIN each path, so the ref's oracle
    # is retrieve_ref, not the kernel
    rwant = retrieve_ref(deq.values, deq.indices, inv, qq, n=7)
    rgot = retrieve_quantized_ref(qc.q_values, qc.indices, qc.scales, inv,
                                  qq, n=7)
    np.testing.assert_array_equal(np.asarray(rgot[0]), np.asarray(rwant[0]))
    np.testing.assert_array_equal(np.asarray(rgot[1]), np.asarray(rwant[1]))


# ragged N/Q, Q > the ref q_chunk (chunked densify), duplicate query indices
@pytest.mark.parametrize("n,q,topn,idx_hi", [(64, 9, 64, None),
                                             (1000, 13, 10, None),
                                             (300, 150, 7, None),
                                             (200, 11, 9, 9)])
def test_quantized_sparse_q_bit_identical(n, q, topn, idx_hi):
    """Quantized candidates × sparse query codes (kernel AND ref) must be
    bit-identical to the fp32 sparse-query generation over the dequantized
    index — including duplicate indices inside query code rows."""
    kq = 8
    qc, deq, inv, _ = _quantized_case(n, q, kq, 256, seed=n + q)
    ks = jax.random.split(jax.random.PRNGKey(n * q + 1), 2)
    qv = jax.random.normal(ks[0], (q, kq), jnp.float32)
    qi = jax.random.randint(ks[1], (q, kq), 0, idx_hi or 256, dtype=jnp.int32)
    want_v, want_i = fused_retrieve_sparse_q(
        deq.values, deq.indices, inv, qv, qi, 256, n=topn
    )
    got_v, got_i = fused_retrieve_quantized_sparse_q(
        qc.q_values, qc.indices, qc.scales, inv, qv, qi, 256, n=topn
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    rwant = retrieve_sparse_q_ref(deq.values, deq.indices, inv, qv, qi, 256,
                                  n=topn)
    rgot = retrieve_quantized_sparse_q_ref(
        qc.q_values, qc.indices, qc.scales, inv, qv, qi, 256, n=topn
    )
    np.testing.assert_array_equal(np.asarray(rgot[0]), np.asarray(rwant[0]))
    np.testing.assert_array_equal(np.asarray(rgot[1]), np.asarray(rwant[1]))


def test_quantized_single_query_and_validation():
    qc, deq, inv, qq = _quantized_case(96, 1, 8, 128, seed=11)
    v, i = fused_retrieve_quantized(qc.q_values, qc.indices, qc.scales, inv,
                                    qq[0], n=96)
    assert v.shape == (96,) and i.shape == (96,)
    assert sorted(np.asarray(i).tolist()) == list(range(96))
    with pytest.raises(ValueError):
        fused_retrieve_quantized(qc.q_values, qc.indices, qc.scales, inv,
                                 qq, n=97)
    qv = jnp.zeros((1, 8)); qi = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError):
        fused_retrieve_quantized_sparse_q(
            qc.q_values, qc.indices, qc.scales, inv, qv, qi, 128, n=97
        )


# -------------------------------------------- fused_retrieve_quantized_mxu
# Generation 5 is APPROXIMATE vs the exact quantized path, but its kernel
# and chunked jnp ref must be BIT-identical to each other: int32
# accumulation is exact/order-invariant and the query-panel quantization
# is one shared function — the only generation where kernel↔ref equality
# is array_equal rather than allclose.
@pytest.mark.parametrize("n,q,topn", [(64, 9, 64), (256, 1, 5),
                                      (1000, 3, 10), (4097, 5, 20),
                                      (300, 150, 7)])
def test_quantized_mxu_kernel_ref_bit_identical(n, q, topn):
    qc, deq, inv, qq = _quantized_case(n, q, 8, 256, seed=n + q)
    got_v, got_i = fused_retrieve_quantized_mxu(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=topn
    )
    ref_v, ref_i = retrieve_quantized_mxu_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=topn
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    # and the ref's candidate blocking cannot change the result either
    blk_v, blk_i = retrieve_quantized_mxu_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=topn, block_n=96
    )
    np.testing.assert_array_equal(np.asarray(blk_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(blk_i), np.asarray(ref_i))


def test_quantized_mxu_close_to_exact_scores():
    """The approximate path's contract vs the exact quantized path is a
    quality bound, not equality: per-element error of the int8 scoring is
    bounded by the two symmetric-quantization steps (≲1% of each side's
    amax), so norm-folded cosine scores must agree to ~1e-2."""
    qc, deq, inv, qq = _quantized_case(512, 6, 8, 256, seed=99)
    ex_v, _ = retrieve_quantized_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=16
    )
    ap_v, _ = retrieve_quantized_mxu_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=16
    )
    np.testing.assert_allclose(np.asarray(ap_v), np.asarray(ex_v), atol=5e-2)
    assert float(np.abs(np.asarray(ap_v) - np.asarray(ex_v)).mean()) < 2e-2


def test_quantized_mxu_tied_scores_match_lax_topk():
    # duplicated candidate rows share a quantization scale AND quantize to
    # identical int8 codes, so int8 scores tie EXACTLY across tile
    # boundaries; the merge must resolve them like lax.top_k (lowest id)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    vals = jnp.tile(jax.random.normal(ks[0], (40, 4), jnp.float32), (8, 1))
    idx = jnp.tile(jax.random.randint(ks[1], (40, 4), 0, 64, jnp.int32), (8, 1))
    qq = jax.random.normal(ks[2], (3, 64), jnp.float32)
    qc = quantize_codes(SparseCodes(values=vals, indices=idx, dim=64))
    deq = dequantize_codes(qc)
    inv = 1.0 / jnp.maximum(jnp.linalg.norm(deq.values, axis=-1), 1e-8)
    got_v, got_i = fused_retrieve_quantized_mxu(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=17,
        block_n=64, block_q=2,
    )
    ref_v, ref_i = retrieve_quantized_mxu_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=17, block_n=96
    )
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))
    # within a tied run, ids must come out ascending (lowest id wins)
    gi = np.asarray(got_i)
    gv = np.asarray(got_v)
    for row_v, row_i in zip(gv, gi):
        for a in range(16):
            if row_v[a] == row_v[a + 1]:
                assert row_i[a] < row_i[a + 1]


@pytest.mark.parametrize("h,want_dtype", [(256, jnp.int16),
                                          (40000, jnp.int16),
                                          (70000, jnp.int32)])
def test_quantized_mxu_int16_wraparound(h, want_dtype):
    """The int8-scoring path shares the low-16-bit index widen: indices in
    the two's-complement wrap region (h=40000) and the int32 fallback
    (h >= 65536) must stay kernel↔ref bit-identical."""
    qc, deq, inv, qq = _quantized_case(300, 2, 8, h, seed=h)
    assert qc.indices.dtype == want_dtype
    got = fused_retrieve_quantized_mxu(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=7
    )
    ref = retrieve_quantized_mxu_ref(
        qc.q_values, qc.indices, qc.scales, inv, qq, n=7
    )
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


# ragged N/Q, Q > the ref q_chunk (chunked densify+quantize), duplicate
# query indices (densify-then-quantize must share the scatter-add order)
@pytest.mark.parametrize("n,q,topn,idx_hi", [(64, 9, 64, None),
                                             (1000, 13, 10, None),
                                             (300, 150, 7, None),
                                             (200, 11, 9, 9)])
def test_quantized_mxu_sparse_q_bit_identical(n, q, topn, idx_hi):
    kq = 8
    qc, deq, inv, _ = _quantized_case(n, q, kq, 256, seed=n + q)
    ks = jax.random.split(jax.random.PRNGKey(n * q + 1), 2)
    qv = jax.random.normal(ks[0], (q, kq), jnp.float32)
    qi = jax.random.randint(ks[1], (q, kq), 0, idx_hi or 256, dtype=jnp.int32)
    got_v, got_i = fused_retrieve_quantized_mxu_sparse_q(
        qc.q_values, qc.indices, qc.scales, inv, qv, qi, 256, n=topn
    )
    ref_v, ref_i = retrieve_quantized_mxu_sparse_q_ref(
        qc.q_values, qc.indices, qc.scales, inv, qv, qi, 256, n=topn
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))
    # the sparse-q path must equal densify + the dense-query mxu path:
    # same panel values -> same quantized panel -> same int8 scores
    qd = _densify(qv, qi, 256)
    dn_v, dn_i = fused_retrieve_quantized_mxu(
        qc.q_values, qc.indices, qc.scales, inv, qd, n=topn
    )
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(dn_v))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(dn_i))


def test_quantize_panel_matches_quantize_codes_arithmetic():
    """The shared panel quantizer must reproduce quantize_codes' value
    arithmetic exactly (same scale floor, rounding, clip) — it is the
    reason the offline and online int8 representations agree."""
    vals = jax.random.normal(jax.random.PRNGKey(0), (5, 16), jnp.float32)
    idx = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None], (5, 16))
    qc = quantize_codes(SparseCodes(values=vals, indices=idx, dim=16))
    qi8, qs = _quantize_panel(vals)
    np.testing.assert_array_equal(np.asarray(qi8), np.asarray(qc.q_values))
    np.testing.assert_array_equal(np.asarray(qs[:, 0]), np.asarray(qc.scales))
    # zero rows (query padding) quantize to zeros with the floored scale
    zi8, zs = _quantize_panel(jnp.zeros((2, 8), jnp.float32))
    assert (np.asarray(zi8) == 0).all() and (np.asarray(zs) == 1e-12).all()


def test_quantized_mxu_single_query_and_validation():
    qc, deq, inv, qq = _quantized_case(96, 1, 8, 128, seed=11)
    v, i = fused_retrieve_quantized_mxu(
        qc.q_values, qc.indices, qc.scales, inv, qq[0], n=96
    )
    assert v.shape == (96,) and i.shape == (96,)
    assert sorted(np.asarray(i).tolist()) == list(range(96))
    with pytest.raises(ValueError):
        fused_retrieve_quantized_mxu(
            qc.q_values, qc.indices, qc.scales, inv, qq, n=97
        )
    qv = jnp.zeros((1, 8)); qi = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError):
        fused_retrieve_quantized_mxu_sparse_q(
            qc.q_values, qc.indices, qc.scales, inv, qv, qi, 128, n=97
        )


# ------------------------------------------- streaming top-n merge identity
# The tile epilogue the kernels ran before the threshold-gated insertion:
# n select-max-and-mask rounds over [best | tile] for every merging tile.
# Kept as the oracle of bit-identity (values, ids, -inf slots included).
def _sweep_merge_top_n(best_v, best_i, tile_v, tile_i, n):
    bq, bn = tile_v.shape
    width = n + bn
    pos_b = jax.lax.broadcasted_iota(jnp.int32, (bq, n), 1)
    pos_t = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1) + n

    def first(v, pos, m):
        return jnp.min(jnp.where(v == m, pos, width), axis=1, keepdims=True)

    def take(pos, at, ids):
        return jnp.sum(jnp.where(pos == at, ids, 0), axis=1, keepdims=True)

    def step(j, carry):
        bv, tv, out_v, out_i = carry
        m = jnp.maximum(jnp.max(bv, axis=1, keepdims=True),
                        jnp.max(tv, axis=1, keepdims=True))
        at = jnp.minimum(first(bv, pos_b, m), first(tv, pos_t, m))
        sel = take(pos_b, at, best_i) + take(pos_t, at, tile_i)
        out_v = jnp.where(pos_b == j, m, out_v)
        out_i = jnp.where(pos_b == j, sel, out_i)
        return (jnp.where(pos_b == at, -jnp.inf, bv),
                jnp.where(pos_t == at, -jnp.inf, tv), out_v, out_i)

    _, _, out_v, out_i = jax.lax.fori_loop(
        0, n, step, (best_v, tile_v, best_v, best_i))
    return out_v, out_i


def _sweep_mask_fold_merge(scores, inv, nb, out_v_ref, out_i_ref, *,
                           n, n_valid, block_n, alive=None, stats_ref=None):
    scores = scores * inv
    bq, bn = scores.shape
    ids = nb * block_n + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    keep = ids < n_valid
    if alive is not None:
        keep = keep & (alive > 0.0)
    scores = jnp.where(keep, scores, -jnp.inf)

    @pl.when(jnp.any(scores > out_v_ref[:, n - 1:n]))
    def _merge():
        out_v_ref[...], out_i_ref[...] = _sweep_merge_top_n(
            out_v_ref[...], out_i_ref[...], scores, ids, n)


@pytest.fixture
def with_sweep_epilogue(monkeypatch):
    """Run a kernel wrapper with the select-max sweep epilogue swapped in
    (traces are cleared on both sides, so nothing cached crosses over)."""
    def run(fn, *args, **kw):
        jax.clear_caches()
        with monkeypatch.context() as m:
            m.setattr(sparse_dot_kernel, "_mask_fold_merge",
                      _sweep_mask_fold_merge)
            out = jax.block_until_ready(fn(*args, **kw))
        jax.clear_caches()
        return out
    return run


MERGE_H, MERGE_N = 128, 16 * 256 + 37   # >= 16 tiles, ragged last tile


def _merge_case(order, q=9, seed=0):
    """Integer-valued codes over 16 latents with unit norms, so every score
    is an exact small integer (no rounding: lax.top_k on numpy scores is
    exact) and ties are common.  Row order: "random", "ascending" /
    "descending" by query 0's score (every tile inserts n / almost none
    merges), "tied" (every row the same code), "repeated" (a 40-row block
    repeated: ties across tile boundaries).  Returns values, ids, query
    codes and the exact (q, N) scores."""
    rng = np.random.default_rng(seed)
    k = 4
    vals = rng.integers(-8, 9, (MERGE_N, k)).astype(np.float32)
    idx = rng.integers(0, 16, (MERGE_N, k)).astype(np.int32)
    if order == "tied":
        vals[:], idx[:] = vals[0], idx[0]
    elif order == "repeated":
        vals, idx = np.resize(vals[:40], vals.shape), np.resize(idx[:40],
                                                                 idx.shape)
    qv = rng.integers(-8, 9, (q, k)).astype(np.float32)
    qi = rng.integers(0, 16, (q, k)).astype(np.int32)
    dense = np.zeros((q, MERGE_H), np.float32)
    np.add.at(dense, (np.arange(q)[:, None], qi), qv)

    def exact(v, i):
        return np.einsum("nk,qnk->qn", v, dense[:, i])

    if order in ("ascending", "descending"):
        s0 = exact(vals, idx)[0]
        perm = np.argsort(-s0 if order == "descending" else s0, kind="stable")
        vals, idx = vals[perm], idx[perm]
    return vals, idx, qv, qi, exact(vals, idx)


def _merge_retrieve(gen, vals, idx, qv, qi, topn, alive=None, **kw):
    """The sparse-query generation ``gen`` on the integer case (unit norms,
    unit dequant scales), through its public ops wrapper."""
    n_rows, inv = vals.shape[0], jnp.ones(vals.shape[0], jnp.float32)
    if gen == 3:
        return fused_retrieve_sparse_q(vals, idx, inv, qv, qi, MERGE_H,
                                       n=topn, alive=alive, **kw)
    if gen == 6:   # the same catalog gathered for every query
        v, i, w = (jnp.broadcast_to(a, (qv.shape[0],) + a.shape)
                   for a in (vals, idx, inv))
        return fused_retrieve_gathered_sparse_q(v, i, w, qv, qi, MERGE_H,
                                                n=topn)
    q8, i16 = vals.astype(np.int8), idx.astype(np.int16)
    ones = jnp.ones(n_rows, jnp.float32)
    fn = (fused_retrieve_quantized_sparse_q if gen == 4
          else fused_retrieve_quantized_mxu_sparse_q)
    return fn(q8, i16, ones, inv, qv, qi, MERGE_H, n=topn, alive=alive, **kw)


def _alive_mask(deleted):
    """"tiles": tiles 0 and 3 deleted whole, every other row of tile 5;
    "most": 62 rows alive, one in 67, so most top-n slots stay -inf."""
    if deleted is None:
        return None
    alive = np.ones(MERGE_N, np.float32)
    if deleted == "tiles":
        alive[0:256] = alive[768:1024] = 0.0
        alive[1280:1536:2] = 0.0
    else:
        alive[np.arange(MERGE_N) % 67 != 5] = 0.0
    return alive


_MERGE_CASES = [("random", 100, None), ("ascending", 100, None),
                ("descending", 100, None), ("tied", 100, None),
                ("repeated", 100, None), ("random", 300, None),
                ("random", 100, "tiles"), ("descending", 100, "most")]


@pytest.mark.parametrize("gen,order,topn,deleted", [
    (gen, *case) for gen in (3, 4, 5, 6) for case in _MERGE_CASES
    if not (gen == 6 and case[2])   # gathered panels carry no alive mask
])
def test_threshold_merge_identical_to_sweep_and_top_k(
        with_sweep_epilogue, gen, order, topn, deleted):
    """The threshold-gated insertion gives exactly what the n-round
    select-max sweep gave (scores, ids, -inf slots) and lax.top_k's answer
    (lowest id first on ties), across catalog orders, top-n wider than a
    tile, a ragged last tile, deletions that empty whole tiles, and fewer
    live rows than n.  The second query panel is one real row padded with
    zero rows."""
    vals, idx, qv, qi, exact = _merge_case(order)
    alive = _alive_mask(deleted)
    got_v, got_i = map(np.asarray,
                       _merge_retrieve(gen, vals, idx, qv, qi, topn, alive))
    was_v, was_i = map(np.asarray, with_sweep_epilogue(
        _merge_retrieve, gen, vals, idx, qv, qi, topn, alive))
    np.testing.assert_array_equal(got_v, was_v)
    np.testing.assert_array_equal(got_i, was_i)

    if gen == 5:   # int8 scoring: its ref is the bit-identical answer
        want_v, want_i = map(np.asarray, retrieve_quantized_mxu_sparse_q_ref(
            vals.astype(np.int8), idx.astype(np.int16),
            jnp.ones(MERGE_N), jnp.ones(MERGE_N), qv, qi, MERGE_H, n=topn,
            alive=alive))
    else:
        s = exact if alive is None else np.where(alive > 0, exact, -np.inf)
        want_v, want_i = map(np.asarray, jax.lax.top_k(jnp.asarray(s), topn))
    live = np.isfinite(want_v)
    np.testing.assert_array_equal(np.isfinite(got_v), live)
    np.testing.assert_array_equal(got_v[live], want_v[live])
    np.testing.assert_array_equal(got_i[live], want_i[live])


def _numpy_merge_counts(scores, topn, block_q=8, block_n=256):
    """Per query panel of the (Q, N) exact scores (rows padded to the
    panel with zero-query rows, which score 0): tiles in which some row
    has a score above its running n-th best, and the insertion rounds
    those tiles need (the largest per-row count above it, at most n)."""
    q, n_rows = scores.shape
    rows = -(-q // block_q) * block_q
    s = np.zeros((rows, -(-n_rows // block_n) * block_n), np.float32)
    s[:, n_rows:] = -np.inf
    s[:q, :n_rows] = scores
    out = []
    for p in range(0, rows, block_q):
        panel = s[p:p + block_q]
        thr = np.full((block_q, 1), -np.inf, np.float32)
        merged = rounds = 0
        for t in range(0, s.shape[1], block_n):
            above = (panel[:, t:t + block_n] > thr).sum(axis=1)
            if above.any():
                merged += 1
                rounds += min(topn, int(above.max()))
            seen = np.sort(panel[:, :t + block_n], axis=1)[:, ::-1]
            if seen.shape[1] >= topn:
                thr = seen[:, topn - 1:topn]
        out.append((merged, rounds))
    return np.array(out)


@pytest.mark.parametrize("gen", [3, 4])
@pytest.mark.parametrize("order", ["random", "descending"])
def test_merge_counters_match_numpy(gen, order):
    """``with_stats=True`` counts, per query panel, the tiles that ran the
    merge and the rounds they ran — a numpy count of the same over exact
    scores.  Over the descending catalog, with one query in every row, a
    panel merges its first tile only.  The outputs are the same with and
    without the counters."""
    vals, idx, qv, qi, exact = _merge_case(order)
    if order == "descending":
        qv, qi, exact = (np.repeat(a[:1], 9, axis=0) for a in (qv, qi, exact))
    v, i, stats = _merge_retrieve(gen, vals, idx, qv, qi, 100,
                                  with_stats=True)
    stats = np.asarray(stats)
    np.testing.assert_array_equal(stats, _numpy_merge_counts(exact, 100))
    if order == "descending":
        np.testing.assert_array_equal(stats[:, 0], [1, 1])
    plain_v, plain_i = _merge_retrieve(gen, vals, idx, qv, qi, 100)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(plain_v))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(plain_i))


# ------------------------------------------------------------------ topk_mask
@pytest.mark.parametrize("b,h,k", [(8, 128, 4), (300, 512, 16), (64, 4096, 32), (257, 640, 1)])
def test_topk_mask_shapes(b, h, k):
    x = jax.random.normal(jax.random.PRNGKey(b + h + k), (b, h))
    np.testing.assert_allclose(topk_mask(x, k), topk_mask_ref(x, k), rtol=1e-6)


def test_topk_mask_leading_dims():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 6, 256))
    np.testing.assert_allclose(topk_mask(x, 8), topk_mask_ref(x, 8), rtol=1e-6)


def test_topk_mask_ties_match_lax_topk():
    # Repeated |values|: kernel must break ties toward the lowest index,
    # exactly like jax.lax.top_k on |x|.
    x = jnp.array([[2.0, -2.0, 2.0, 1.0, -2.0, 0.5]] * 8)
    np.testing.assert_allclose(topk_mask(x, 3), topk_mask_ref(x, 3), rtol=0)


# --------------------------------------------------------------- fused_encode
@pytest.mark.parametrize("b,d,h,k", [(64, 96, 512, 8), (200, 64, 256, 4), (128, 768, 1024, 32)])
def test_fused_encode_matches_ref(b, d, h, k):
    key = jax.random.PRNGKey(b + d)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (b, d))
    w = jax.random.normal(k2, (d, h)) / np.sqrt(d)
    bias = 0.01 * jax.random.normal(k3, (h,))
    codes = fused_encode(x, w, bias, k)
    rv, ri = fused_encode_ref(normalize_input(x), w, bias, k)
    # same selected index SET per row, and same (index -> value) mapping
    got = {}
    for r in range(b):
        gi = np.asarray(codes.indices[r])
        ri_r = np.asarray(ri[r])
        assert set(gi.tolist()) == set(ri_r.tolist()), f"row {r} index set differs"
    # values agree after aligning by index
    dense_got = np.zeros((b, h), np.float32)
    dense_want = np.zeros((b, h), np.float32)
    bidx = np.arange(b)[:, None]
    dense_got[bidx, np.asarray(codes.indices)] = np.asarray(codes.values)
    dense_want[bidx, np.asarray(ri)] = np.asarray(rv)
    np.testing.assert_allclose(dense_got, dense_want, rtol=1e-4, atol=1e-5)


def test_fused_encode_agrees_with_core_encode():
    from repro.core import SAEConfig, encode, init_params
    from repro.core import sparse as sp

    cfg = SAEConfig(d=64, h=256, k=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d))
    a = encode(params, x, cfg.k)
    b = fused_encode(x, params["w_enc"], params["b_enc"], cfg.k)
    np.testing.assert_allclose(sp.densify(a), sp.densify(b), rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------- embedding_bag
@pytest.mark.parametrize("v,dim,b,l", [(100, 32, 16, 1), (1000, 64, 37, 5), (5000, 128, 8, 20)])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_shapes(v, dim, b, l, mode):
    kt, ki = jax.random.split(jax.random.PRNGKey(v + b), 2)
    table = jax.random.normal(kt, (v, dim))
    ids = jax.random.randint(ki, (b, l), -1, v, dtype=jnp.int32)  # -1 = pad
    np.testing.assert_allclose(
        embedding_bag(table, ids, mode),
        embedding_bag_ref(table, ids, mode),
        rtol=1e-5,
        atol=1e-5,
    )


def test_embedding_bag_all_padding_row():
    table = jax.random.normal(jax.random.PRNGKey(0), (10, 16))
    ids = jnp.full((3, 4), -1, jnp.int32)
    out = embedding_bag(table, ids, "mean")
    np.testing.assert_allclose(out, np.zeros((3, 16)), atol=1e-7)


def test_embedding_bag_bf16_table():
    table = jax.random.normal(jax.random.PRNGKey(0), (50, 32)).astype(jnp.bfloat16)
    ids = jax.random.randint(jax.random.PRNGKey(1), (7, 3), 0, 50, dtype=jnp.int32)
    got = embedding_bag(table, ids, "sum").astype(jnp.float32)
    want = embedding_bag_ref(table, ids, "sum").astype(jnp.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
