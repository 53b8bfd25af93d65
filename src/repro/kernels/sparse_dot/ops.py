"""Public jit'd wrappers for the sparse_dot kernel family.

Pads N (and Q) up to tile sizes, dispatches to the Pallas kernels
(interpret=True on CPU so the kernel bodies themselves are what run in
tests), and exposes the same contracts as ref.sparse_dot_ref /
ref.retrieve_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.sparse_dot.kernel import (
    BLOCK_N,
    BLOCK_Q,
    fused_retrieve_gathered_quantized_mxu_sparse_q_pallas,
    fused_retrieve_gathered_quantized_sparse_q_pallas,
    fused_retrieve_gathered_sparse_q_pallas,
    fused_retrieve_pallas,
    fused_retrieve_quantized_mxu_pallas,
    fused_retrieve_quantized_mxu_sparse_q_pallas,
    fused_retrieve_quantized_pallas,
    fused_retrieve_quantized_sparse_q_pallas,
    fused_retrieve_sparse_q_pallas,
    sparse_dot_pallas,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("block_n", "block_q"))
def sparse_dot(
    values: jax.Array,
    indices: jax.Array,
    q: jax.Array,
    *,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> jax.Array:
    """scores (Q, N): fixed-k sparse candidates scored against dense queries.

    values (N, k) float32, indices (N, k) int32, q (Q, h) or (h,) float32.
    """
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    n, k = values.shape
    nq = q.shape[0]
    pad = (-n) % block_n
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        indices = jnp.pad(indices, ((0, pad), (0, 0)))
    qpad = (-nq) % block_q
    if qpad:
        q = jnp.pad(q, ((0, qpad), (0, 0)))
    out = sparse_dot_pallas(
        values, indices, q,
        interpret=not _on_tpu(), block_n=block_n, block_q=block_q,
    )
    out = out[:nq, :n]
    return out[0] if squeeze else out


def _pad_candidates(values, indices, inv_norms, block_n, scales=None,
                    alive=None):
    """Zero-pad the candidate axis up to a tile multiple — the one padding
    scheme every retrieve wrapper shares (fp32 and quantized alike).
    Padded rows carry value/scale 0 and inv-norm 0 (and alive 0, i.e.
    dead), and are additionally masked to -inf by global id (``n_valid``)
    inside the kernels."""
    n_valid = values.shape[0]
    pad = (-n_valid) % block_n
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        indices = jnp.pad(indices, ((0, pad), (0, 0)))
        inv_norms = jnp.pad(inv_norms, (0, pad))
        if scales is not None:
            scales = jnp.pad(scales, (0, pad))
        if alive is not None:
            alive = jnp.pad(alive, (0, pad))
    return values, indices, inv_norms, scales, alive, n_valid


@functools.partial(
    jax.jit, static_argnames=("n", "block_n", "block_q", "interpret")
)
def fused_retrieve(
    values: jax.Array,
    indices: jax.Array,
    inv_norms: jax.Array,
    q: jax.Array,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused score+select -> ((Q, n) norm-folded scores, (Q, n) ids).

    values (N, k) f32, indices (N, k) i32, inv_norms (N,) f32 reciprocal
    candidate norms, q (Q, h) or (h,) f32.  n must not exceed N.  The
    (Q, N) score matrix is never materialized — only (Q, n) reaches HBM.
    """
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    if n > values.shape[0]:
        raise ValueError(f"top-n {n} exceeds candidate count {values.shape[0]}")
    nq = q.shape[0]
    values, indices, inv_norms, _, _, n_valid = _pad_candidates(
        values, indices, inv_norms, block_n
    )
    qpad = (-nq) % block_q
    if qpad:
        q = jnp.pad(q, ((0, qpad), (0, 0)))
    out_v, out_i = fused_retrieve_pallas(
        values,
        indices,
        inv_norms.astype(jnp.float32).reshape(-1, 1),
        q,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
    )
    out_v, out_i = out_v[:nq], out_i[:nq]
    return (out_v[0], out_i[0]) if squeeze else (out_v, out_i)


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "block_n", "block_q", "interpret",
                     "with_stats"),
)
def fused_retrieve_sparse_q(
    values: jax.Array,
    indices: jax.Array,
    inv_norms: jax.Array,
    q_values: jax.Array,
    q_indices: jax.Array,
    h: int,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
    alive: jax.Array | None = None,
    with_stats: bool = False,
) -> tuple[jax.Array, ...]:
    """Sparse-query fused score+select -> ((Q, n) scores, (Q, n) ids).

    values (N, k) f32, indices (N, k) i32, inv_norms (N,) f32, q_values
    (Q, kq) or (kq,) f32 + matching q_indices i32 — k-sparse query codes
    over [0, h), e.g. straight from ``fused_encode``.  Bit-identical to
    ``fused_retrieve(values, indices, inv_norms, densify(q), n=n)``, but
    only the (Q, kq) codes ever touch HBM on the query side.  ``alive``:
    optional (N,) 1.0/0.0 row-liveness mask (segmented-index deletions) —
    dead rows are masked to -inf exactly like padding, so they can never
    appear among the top-n while live rows' scores/ids are untouched.
    ``with_stats=True`` appends the kernel's merge counters, a
    (ceil(Q / block_q), 2) int32 array: per query panel, the tiles that
    ran the top-n merge and the insertion rounds they ran.
    """
    squeeze = q_values.ndim == 1
    if squeeze:
        q_values, q_indices = q_values[None], q_indices[None]
    if n > values.shape[0]:
        raise ValueError(f"top-n {n} exceeds candidate count {values.shape[0]}")
    nq = q_values.shape[0]
    values, indices, inv_norms, _, alive, n_valid = _pad_candidates(
        values, indices, inv_norms, block_n, alive=alive
    )
    qpad = (-nq) % block_q
    if qpad:
        q_values = jnp.pad(q_values, ((0, qpad), (0, 0)))
        q_indices = jnp.pad(q_indices, ((0, qpad), (0, 0)))
    out_v, out_i, *stats = fused_retrieve_sparse_q_pallas(
        values,
        indices,
        inv_norms.astype(jnp.float32).reshape(-1, 1),
        q_values,
        q_indices,
        h,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
        alive=(None if alive is None
               else alive.astype(jnp.float32).reshape(-1, 1)),
        with_stats=with_stats,
    )
    out_v, out_i = out_v[:nq], out_i[:nq]
    out = (out_v[0], out_i[0]) if squeeze else (out_v, out_i)
    return out + tuple(stats)


@functools.partial(
    jax.jit, static_argnames=("n", "block_n", "block_q", "interpret")
)
def fused_retrieve_quantized(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    q: jax.Array,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Quantized-index fused score+select -> ((Q, n) scores, (Q, n) ids).

    q_values (N, k) int8, indices (N, k) int16/int32, scales (N,) f32
    per-row dequant scales, inv_norms (N,) f32, q (Q, h) or (h,) f32.
    The index streams from HBM in its quantized dtypes and is dequantized
    per tile in VMEM — bit-identical to
    ``fused_retrieve(dequantize(q_values, scales), widen(indices), ...)``
    without ever materializing that fp32 copy.
    """
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    if n > q_values.shape[0]:
        raise ValueError(
            f"top-n {n} exceeds candidate count {q_values.shape[0]}"
        )
    nq = q.shape[0]
    q_values, indices, inv_norms, scales, _, n_valid = _pad_candidates(
        q_values, indices, inv_norms, block_n, scales
    )
    qpad = (-nq) % block_q
    if qpad:
        q = jnp.pad(q, ((0, qpad), (0, 0)))
    out_v, out_i = fused_retrieve_quantized_pallas(
        q_values,
        indices,
        scales.astype(jnp.float32).reshape(-1, 1),
        inv_norms.astype(jnp.float32).reshape(-1, 1),
        q,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
    )
    out_v, out_i = out_v[:nq], out_i[:nq]
    return (out_v[0], out_i[0]) if squeeze else (out_v, out_i)


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "block_n", "block_q", "interpret",
                     "with_stats"),
)
def fused_retrieve_quantized_sparse_q(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
    alive: jax.Array | None = None,
    with_stats: bool = False,
) -> tuple[jax.Array, ...]:
    """Quantized candidates × sparse query codes -> ((Q, n) scores, ids).

    The full-compression serving kernel: candidate tiles stream int8/int16
    and dequantize in VMEM, query codes densify into VMEM scratch.  Only
    the (Q, kq) codes and (Q, n) results touch HBM on the query side, and
    the index never exists in fp32.  Bit-identical to
    ``fused_retrieve_sparse_q`` over the dequantized arrays.  ``alive``:
    optional (N,) 1.0/0.0 row-liveness mask, ``with_stats``: the merge
    counters (see ``fused_retrieve_sparse_q`` for both).
    """
    squeeze = query_values.ndim == 1
    if squeeze:
        query_values, query_indices = query_values[None], query_indices[None]
    if n > q_values.shape[0]:
        raise ValueError(
            f"top-n {n} exceeds candidate count {q_values.shape[0]}"
        )
    nq = query_values.shape[0]
    q_values, indices, inv_norms, scales, alive, n_valid = _pad_candidates(
        q_values, indices, inv_norms, block_n, scales, alive=alive
    )
    qpad = (-nq) % block_q
    if qpad:
        query_values = jnp.pad(query_values, ((0, qpad), (0, 0)))
        query_indices = jnp.pad(query_indices, ((0, qpad), (0, 0)))
    out_v, out_i, *stats = fused_retrieve_quantized_sparse_q_pallas(
        q_values,
        indices,
        scales.astype(jnp.float32).reshape(-1, 1),
        inv_norms.astype(jnp.float32).reshape(-1, 1),
        query_values,
        query_indices,
        h,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
        alive=(None if alive is None
               else alive.astype(jnp.float32).reshape(-1, 1)),
        with_stats=with_stats,
    )
    out_v, out_i = out_v[:nq], out_i[:nq]
    out = (out_v[0], out_i[0]) if squeeze else (out_v, out_i)
    return out + tuple(stats)


@functools.partial(
    jax.jit, static_argnames=("n", "block_n", "block_q", "interpret")
)
def fused_retrieve_quantized_mxu(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    q: jax.Array,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Int8-scoring fused score+select (generation 5, APPROXIMATE).

    Same operands/padding contract as ``fused_retrieve_quantized``, but
    candidate tiles are scored in int8 (query panel quantized per panel in
    VMEM, int32 accumulation, one f32 rescale in the merge) instead of
    being dequantized.  Bit-identical to ``retrieve_quantized_mxu_ref``;
    quality vs the exact quantized path is a measured bound
    (``repro.core.eval``), not an equality.
    """
    squeeze = q.ndim == 1
    if squeeze:
        q = q[None]
    if n > q_values.shape[0]:
        raise ValueError(
            f"top-n {n} exceeds candidate count {q_values.shape[0]}"
        )
    nq = q.shape[0]
    q_values, indices, inv_norms, scales, _, n_valid = _pad_candidates(
        q_values, indices, inv_norms, block_n, scales
    )
    qpad = (-nq) % block_q
    if qpad:
        q = jnp.pad(q, ((0, qpad), (0, 0)))
    out_v, out_i = fused_retrieve_quantized_mxu_pallas(
        q_values,
        indices,
        scales.astype(jnp.float32).reshape(-1, 1),
        inv_norms.astype(jnp.float32).reshape(-1, 1),
        q,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
    )
    out_v, out_i = out_v[:nq], out_i[:nq]
    return (out_v[0], out_i[0]) if squeeze else (out_v, out_i)


@functools.partial(
    jax.jit, static_argnames=("h", "n", "block_n", "block_q", "interpret")
)
def fused_retrieve_quantized_mxu_sparse_q(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
    alive: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Int8-scoring × sparse query codes (generation 5, APPROXIMATE): the
    no-dequant full-compression serving op.  Codes densify + quantize into
    VMEM scratch once per panel; candidates stream and score in int8.
    Bit-identical to ``retrieve_quantized_mxu_sparse_q_ref``.  ``alive``:
    optional (N,) 1.0/0.0 row-liveness mask (see
    ``fused_retrieve_sparse_q``).
    """
    squeeze = query_values.ndim == 1
    if squeeze:
        query_values, query_indices = query_values[None], query_indices[None]
    if n > q_values.shape[0]:
        raise ValueError(
            f"top-n {n} exceeds candidate count {q_values.shape[0]}"
        )
    nq = query_values.shape[0]
    q_values, indices, inv_norms, scales, alive, n_valid = _pad_candidates(
        q_values, indices, inv_norms, block_n, scales, alive=alive
    )
    qpad = (-nq) % block_q
    if qpad:
        query_values = jnp.pad(query_values, ((0, qpad), (0, 0)))
        query_indices = jnp.pad(query_indices, ((0, qpad), (0, 0)))
    out_v, out_i = fused_retrieve_quantized_mxu_sparse_q_pallas(
        q_values,
        indices,
        scales.astype(jnp.float32).reshape(-1, 1),
        inv_norms.astype(jnp.float32).reshape(-1, 1),
        query_values,
        query_indices,
        h,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
        alive=(None if alive is None
               else alive.astype(jnp.float32).reshape(-1, 1)),
    )
    out_v, out_i = out_v[:nq], out_i[:nq]
    return (out_v[0], out_i[0]) if squeeze else (out_v, out_i)


def _pad_gathered(block_n, block_q, nq, *arrays):
    """Pad per-query candidate panels for the gathered kernels: the
    candidate axis (axis 1) up to a ``block_n`` multiple on every array,
    then the query axis (axis 0) up to a ``block_q`` multiple — query
    padding covers the candidate panels too, since every input now carries
    the leading Q axis.  Returns (padded arrays..., n_valid)."""
    n_valid = arrays[0].shape[1]
    pad = (-n_valid) % block_n
    qpad = (-nq) % block_q

    def p(a):
        widths = [(0, qpad), (0, pad)] + [(0, 0)] * (a.ndim - 2)
        return jnp.pad(a, widths) if (pad or qpad) else a

    return tuple(p(a) for a in arrays) + (n_valid,)


@functools.partial(
    jax.jit, static_argnames=("h", "n", "block_n", "block_q", "interpret")
)
def fused_retrieve_gathered_sparse_q(
    values: jax.Array,
    indices: jax.Array,
    inv_norms: jax.Array,
    q_values: jax.Array,
    q_indices: jax.Array,
    h: int,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gathered sparse-query fused score+select (generation 6, fp32).

    values (Q, B, k) f32 per-query candidate panels, indices (Q, B, k)
    i32, inv_norms (Q, B) f32, q_values/q_indices (Q, kq) query codes over
    [0, h).  Returns ((Q, n) scores, (Q, n) LOCAL candidate positions in
    [0, B)) — the caller maps positions back to catalog rows through its
    stage-1 row table.  Bit-identical per query to
    ``fused_retrieve_sparse_q`` over the gathered sub-arrays.
    """
    if values.ndim != 3:
        raise ValueError(
            f"gathered retrieve expects (Q, B, k) candidate panels, "
            f"got ndim={values.ndim}"
        )
    if n > values.shape[1]:
        raise ValueError(f"top-n {n} exceeds candidate count {values.shape[1]}")
    nq = q_values.shape[0]
    qpad = (-nq) % block_q
    if qpad:
        q_values = jnp.pad(q_values, ((0, qpad), (0, 0)))
        q_indices = jnp.pad(q_indices, ((0, qpad), (0, 0)))
    values, indices, inv_norms, n_valid = _pad_gathered(
        block_n, block_q, nq,
        values, indices, inv_norms.astype(jnp.float32),
    )
    out_v, out_i = fused_retrieve_gathered_sparse_q_pallas(
        values,
        indices,
        inv_norms,
        q_values,
        q_indices,
        h,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
    )
    return out_v[:nq], out_i[:nq]


@functools.partial(
    jax.jit, static_argnames=("h", "n", "block_n", "block_q", "interpret")
)
def fused_retrieve_gathered_quantized_sparse_q(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gathered quantized × sparse query codes (generation 6): per-query
    candidate panels stream in their quantized storage dtypes — q_values
    (Q, B, k) int8, indices (Q, B, k) int16/int32, scales/inv_norms (Q, B)
    f32 — and dequantize per brick in VMEM.  Bit-identical per query to
    ``fused_retrieve_quantized_sparse_q`` over the gathered sub-arrays.
    """
    if q_values.ndim != 3:
        raise ValueError(
            f"gathered retrieve expects (Q, B, k) candidate panels, "
            f"got ndim={q_values.ndim}"
        )
    if n > q_values.shape[1]:
        raise ValueError(
            f"top-n {n} exceeds candidate count {q_values.shape[1]}"
        )
    nq = query_values.shape[0]
    qpad = (-nq) % block_q
    if qpad:
        query_values = jnp.pad(query_values, ((0, qpad), (0, 0)))
        query_indices = jnp.pad(query_indices, ((0, qpad), (0, 0)))
    q_values, indices, scales, inv_norms, n_valid = _pad_gathered(
        block_n, block_q, nq,
        q_values, indices,
        scales.astype(jnp.float32), inv_norms.astype(jnp.float32),
    )
    out_v, out_i = fused_retrieve_gathered_quantized_sparse_q_pallas(
        q_values,
        indices,
        scales,
        inv_norms,
        query_values,
        query_indices,
        h,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
    )
    return out_v[:nq], out_i[:nq]


@functools.partial(
    jax.jit, static_argnames=("h", "n", "block_n", "block_q", "interpret")
)
def fused_retrieve_gathered_quantized_mxu_sparse_q(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gathered int8-scoring × sparse query codes (generation 6 × 5,
    APPROXIMATE vs exact): per-query int8 candidate panels score with
    exact int32 accumulation against the once-per-panel quantized query
    scratch.  Bit-identical per query to
    ``fused_retrieve_quantized_mxu_sparse_q`` over the gathered
    sub-arrays, and to ``retrieve_gathered_quantized_mxu_sparse_q_ref``.
    """
    if q_values.ndim != 3:
        raise ValueError(
            f"gathered retrieve expects (Q, B, k) candidate panels, "
            f"got ndim={q_values.ndim}"
        )
    if n > q_values.shape[1]:
        raise ValueError(
            f"top-n {n} exceeds candidate count {q_values.shape[1]}"
        )
    nq = query_values.shape[0]
    qpad = (-nq) % block_q
    if qpad:
        query_values = jnp.pad(query_values, ((0, qpad), (0, 0)))
        query_indices = jnp.pad(query_indices, ((0, qpad), (0, 0)))
    q_values, indices, scales, inv_norms, n_valid = _pad_gathered(
        block_n, block_q, nq,
        q_values, indices,
        scales.astype(jnp.float32), inv_norms.astype(jnp.float32),
    )
    out_v, out_i = fused_retrieve_gathered_quantized_mxu_sparse_q_pallas(
        q_values,
        indices,
        scales,
        inv_norms,
        query_values,
        query_indices,
        h,
        n=n,
        n_valid=n_valid,
        interpret=not _on_tpu() if interpret is None else interpret,
        block_n=block_n,
        block_q=block_q,
    )
    return out_v[:nq], out_i[:nq]
