"""Scatter-query SpMV Pallas kernels (DESIGN.md §3) — three generations.

Contract (all): scores[qi, i] = Σ_j values[i, j] · q[qi, indices[i, j]]

Generation 1 — ``sparse_dot_pallas`` (blocked, multi-query):
  * A (BLOCK_Q, h) *panel* of dense queries is VMEM-resident per grid step —
    not a single row.  Each (BLOCK_N, k) candidate tile streams HBM→VMEM
    **once per query panel** and is scored against all BLOCK_Q queries, so
    candidate HBM traffic drops by BLOCK_Q× versus the per-query kernel
    (grid (Q, N/BLOCK_N)) this replaces.
  * The gather runs as k rounds (``_score_tile``): the candidate tile is
    transposed once so candidates sit on lanes, and round j's index row
    (BLOCK_N,) addresses the query panel's lanes, FMA'd with the value
    row.  Arithmetic intensity: 2·BLOCK_Q flops per 8 bytes streamed.
  * Grid = (Q/BLOCK_Q, N/BLOCK_N); both axes carry no cross-step state.

Generation 2 — ``fused_retrieve_pallas`` (score + select, streaming top-n):
  * Same blocked scoring, but the (Q, N) score matrix NEVER reaches HBM.
    The per-query-panel running best-(score, id) buffers — shape
    (BLOCK_Q, n) — live in the revisited output block (VMEM-resident across
    the whole candidate axis, index map ignores the candidate grid index)
    and each tile's (BLOCK_Q, BLOCK_N) scores are inserted into them.
    Only (Q, n) values + ids are ever written back.
  * Per-candidate reciprocal norms stream alongside the values
    ((BLOCK_N, 1) tiles) and fold the cosine denominator into the epilogue;
    the per-query 1/‖q‖ factor is applied outside (it cannot reorder a
    query row's top-n).
  * A whole-tile skip: if no score in the tile beats any query's current
    n-th best, the merge is predicated off (`pl.when`) — the common case
    once the buffers warm up on impact-ordered or clustered data.
  * Threshold-gated insertion (``_merge_top_n``): a merging tile runs as
    many rounds as its most-improved row has scores above that row's n-th
    best (at most n), not n.  Each round moves, for every row at once,
    the row's best remaining tile score into its score-descending buffer
    at p = #(buffer >= score) and shifts the tail one lane right; a row
    whose best remaining score cannot enter (p = n) is left as it is.  A
    warm buffer takes one or two rounds; an adversarial order (ascending
    scores) takes n, each round cheaper than one of the n-round
    select-max sweep it replaces.
  * Tie semantics match `jax.lax.top_k` (lowest candidate id wins): tiles
    arrive in ascending-id order, so a buffered score equal to a tile's
    is ahead of it (the insert lands after every equal entry), and within
    a tile each round takes the *first* position attaining the max.
    Slots left at -inf (fewer than n finite candidates so far) carry the
    id that led the buffer before the merge, as the sweep did.
  * Padded candidate rows (N % BLOCK_N) are masked to -inf inside the
    kernel via the static true row count, so they can never surface even
    when all real scores are negative.

Generation 4 — ``fused_retrieve_quantized_pallas`` (+ sparse-query variant):
  * The candidate index streams from HBM in its *quantized* storage dtypes
    — (BLOCK_N, k) int8 values, (BLOCK_N, k) int16/int32 indices, and a
    (BLOCK_N, 1) f32 per-row scale column alongside the reciprocal norms —
    and is dequantized in VMEM (``_dequant_tile``: int8→f32 × scale; int16
    indices widened with the low-16-bit mask that undoes two's-complement
    wrap for h ∈ [32768, 65536)) before the shared scoring + streaming
    top-n epilogue.  Candidate HBM traffic per tile drops from 8k+4 to
    3k+8 bytes/row (~2.6x at k=32) — the compound-compressed format is
    what lives in HBM, not an fp32 copy.
  * Dequantization reproduces ``quantize_codes``'s dequant op-for-op
    (int8→f32 exact, one f32 multiply per element), so the kernel is
    bit-identical — scores, ids, ties — to dequantize-then-
    ``fused_retrieve`` on the same quantized values.  Quantization error
    is a build-time choice, never a serving-path one.
  * ``fused_retrieve_quantized_sparse_q_pallas`` composes generation 3's
    VMEM query densification with the quantized candidate stream: neither
    a dense query panel nor an fp32 index ever exists in HBM.

Generation 5 — ``fused_retrieve_quantized_mxu_pallas`` (+ sparse-query
variant): the APPROXIMATE int8-scoring fast path.
  * Candidate tiles stream in the same quantized storage dtypes as
    generation 4, but are never dequantized: scoring runs int8×int8 with
    int32 accumulation — the int8 MXU's native contraction on real
    hardware (one (BLOCK_Q, BLOCK_N) i32 accumulator, k gather-FMA
    rounds — today on the VPU, see ROADMAP S4), instead of f32 FMAs on
    dequantized tiles.
  * The query panel is quantized ONCE per panel into VMEM scratch
    (``_quantize_panel``: per-row symmetric amax/127, the same arithmetic
    as ``quantize_codes``): int8 codes held widened to an int32
    (BLOCK_Q, h) panel (Mosaic gathers 32-bit lanes only) + (BLOCK_Q, 1)
    f32 scales.  Per tile, the single f32 rescale
    ``(acc·q_scale) · (row_scale·inv_norm)`` folds into the streaming
    ``_mask_fold_merge`` epilogue — no per-element dequant anywhere.
  * Contract change: this is the first generation whose relationship to
    the exact path is a MEASURED QUALITY BOUND (recall@n / score MAE /
    rank displacement via ``repro.core.eval``), not bit-identity.  What
    *is* bit-identical is kernel↔ref: int32 accumulation is exact and
    order-invariant and the panel quantization is the shared
    ``_quantize_panel``, so the chunked jnp ref reproduces the kernel
    exactly — unlike the f32 generations, where kernel and ref only agree
    to rounding.

Generation 3 — ``fused_retrieve_sparse_q_pallas`` (sparse queries in):
  * The scatter-query SpMV from *both* sides: the query panel arrives as
    (BLOCK_Q, kq) (values, indices) sparse codes — the ``fused_encode``
    output — not as a dense (BLOCK_Q, h) expansion.  Only (Q, kq) query
    codes and the (Q, n) results ever touch HBM; the dense panel exists
    solely as a VMEM scratch, rebuilt once per query panel (on the first
    candidate step) by a kq-round comparison-scatter:
        panel[qi, c] = Σ_l q_vals[qi, l] · [q_idx[qi, l] == c]
    accumulated in l order, so duplicate indices within a code row sum
    exactly like ``sparse.densify``'s sequential scatter-add — the whole
    kernel is bit-identical to densify + fused_retrieve.
  * Scoring, streaming top-n epilogue, norm folding, padding masks and tie
    semantics are shared with generation 2 (same ``_score_tile`` /
    ``_mask_fold_merge`` code paths).
  * Query HBM traffic drops from 4·Q·h bytes to 8·Q·kq — h/(2kq) ≈ 64×
    at h=4096, kq=32 — and the request chain fused_encode →
    fused_retrieve_sparse_q never round-trips a dense query through HBM.

VMEM budget per grid step (f32):
    4·BLOCK_Q·h            query panel        (8 × 4096  → 128 KiB)
  + 8·BLOCK_N·k            candidate tile     (256 × 32  →  64 KiB)
  + 4·BLOCK_N              reciprocal norms   (           →   1 KiB)
  + 8·BLOCK_Q·n            output best-(v,id) (8 × 64    →   4 KiB)
  + 8·BLOCK_Q·128⌈n/128⌉   merge buffer temp  (8 × 128   →   8 KiB)
  + 4·BLOCK_Q·BLOCK_N      merge tile temp    (8 × 256   →   8 KiB)
  ≈ 0.25 MiB at defaults — far under the ~16 MiB/core VMEM ceiling; h up
  to ~128k or BLOCK_Q up to ~256 stay in budget.  Generation 3 swaps the
  query-panel *input* block for a same-size (BLOCK_Q, h) scratch plus two
  (BLOCK_Q, kq) code tiles — net VMEM unchanged to first order.

Generation 6 — ``fused_retrieve_gathered_*_pallas`` (gather-aware re-rank):
  * The batched two-stage stage 2.  Candidate arrays carry a leading query
    axis — values/indices (Q, B, k) tiles, inv_norms/scales (Q, B) — i.e.
    each query streams ITS OWN pre-gathered candidate panel (the rows its
    stage-1 union selected), and the returned ids are candidate POSITIONS
    in [0, B), local to each query's panel.  Block specs tile both axes:
    (BLOCK_Q, BLOCK_N, k) candidate bricks, (BLOCK_Q, BLOCK_N) norm/scale
    tiles, grid (Q/BLOCK_Q, B/BLOCK_N) with the candidate axis innermost
    as ever.
  * Scoring swaps the shared index row for a per-row one (the same
    ``_score_tile`` on 3-D bricks): round j's index slab (BLOCK_Q,
    BLOCK_N) addresses each query row's own panel lanes — same gather
    and FMA count.  The epilogue folds a per-(query, candidate) norm tile
    instead of a broadcast norm row; the merge, padding masks,
    whole-tile skip and tie semantics are generation 2's unchanged.
  * Each query row's arithmetic is op-for-op the per-query generation on
    its gathered sub-arrays, so batched stage 2 is bit-identical — scores,
    ids, ties — to Q independent per-query fused calls (the PR 7 path),
    and to the gathered chunked-jnp refs under the usual generation rules
    (mxu exactly, f32 to rounding).
  * Three variants mirror the two-stage-eligible modes: fp32 sparse-q,
    quantized sparse-q, quantized-mxu sparse-q (two-stage is sparse-mode
    only — the query side always arrives as codes).

Lowering notes (Mosaic, checked for a v5e by tests/test_tpu_compile.py;
interpret mode accepts all of the refused forms):
  * No dynamic slice or store on a value's lanes: round j's row is picked
    by a masked reduction (``_pick``), and round j of the merge lands in
    output lane j by a masked select.
  * A lane gather (tpu.dynamic_gather) reads one 128-lane vreg and 32-bit
    data only: ``_lane_gather`` visits the (BLOCK_Q, h) panel one 128-lane
    chunk at a time and keeps the lanes whose index falls in the chunk —
    k·(h/128)·(BLOCK_N/128) gathers per tile; int8 panels are held int32.
  * The merge keeps the buffer and the tile as two arrays.  The buffer is
    padded in-kernel to whole 128-lane vregs so the insertion's one-lane
    shift is a lane rotation (``pltpu.roll``); the rest is max / min /
    sum / where / broadcasted_iota — no in-kernel sort or top_k.  A round
    runs three cross-lane reductions (tile max, its first position, the
    insert position), two of them side by side.
  * The round count is a traced bound: a count of scores above each row's
    n-th best, reduced to one scalar the way ``pl.when`` reduces its test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_dot.ref import _quantize_panel, _widen_idx

BLOCK_N = 256  # candidate rows per tile (8-sublane multiple)
BLOCK_Q = 8    # query rows per VMEM-resident panel
LANES = 128    # vreg lane width: the reach of one in-register lane gather

_NEG_INF = float("-inf")


def _pick(x, j, axis):
    """``x.take(j, axis)`` for a traced ``j`` (kept as a unit axis on 2-D
    input): a masked reduction, since Mosaic lowers no dynamic slice of a
    value.  Exact — every other term is zero."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.sum(jnp.where(pos == j, x, jnp.zeros_like(x)), axis=axis,
                   keepdims=x.ndim == 2)


def _lane_gather(panel, idx):
    """``out[r, i] = panel[r, idx[r, i]]`` for a (R, H) panel, (R, W) idx.

    Mosaic gathers lanes only within one vreg, so the panel is visited in
    128-lane chunks: each chunk gathers at the in-chunk offset and keeps
    the lanes whose index falls inside it (the index axis is cut into
    128-lane pieces the same way).  Nothing touches the gathered values,
    so every output is exactly a panel entry.
    """
    h, w = panel.shape[1], idx.shape[1]
    chunk = jnp.right_shift(idx, LANES.bit_length() - 1)   # idx // LANES
    lane = jnp.bitwise_and(idx, LANES - 1)                # idx % LANES
    pieces = []
    for w0 in range(0, w, LANES):
        c_p, l_p = chunk[:, w0:w0 + LANES], lane[:, w0:w0 + LANES]
        g = jnp.zeros(c_p.shape, panel.dtype)
        for c0 in range(0, h, LANES):
            src = panel[:, c0:c0 + LANES]
            hit = jnp.take_along_axis(src, l_p, axis=1,
                                      mode="promise_in_bounds")
            g = jnp.where(c_p == c0 // LANES, hit, g)
        pieces.append(g)
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


def _score_tile(vals, idx, panel):
    """(BLOCK_Q, BLOCK_N) scores ``Σ_j vals[.., j] · panel[r, idx[.., j]]``,
    accumulated in j order.

    vals/idx: a shared (BLOCK_N, k) candidate tile, or per-query
    (BLOCK_Q, BLOCK_N, k) bricks (generation 6).  panel: (BLOCK_Q, h) f32,
    or int32 holding int8 codes (generation 5: exact int32 accumulation).
    The tile is transposed once so candidates sit on lanes; round j then
    lane-gathers every candidate's j-th panel entry and FMAs its value.
    """
    bq = panel.shape[0]
    vt = jnp.swapaxes(vals.astype(panel.dtype), -1, -2)    # (.., k, BLOCK_N)
    it = jnp.swapaxes(idx, -1, -2)
    k, bn = vt.shape[-2:]

    def body(j, acc):
        cols = jnp.broadcast_to(_pick(it, j, it.ndim - 2), (bq, bn))
        return acc + _lane_gather(panel, cols) * _pick(vt, j, vt.ndim - 2)

    return jax.lax.fori_loop(0, k, body, jnp.zeros((bq, bn), panel.dtype))


def _dot_kernel(vals_ref, idx_ref, q_ref, out_ref):
    out_ref[...] = _score_tile(vals_ref[...], idx_ref[...], q_ref[...])


@functools.partial(
    jax.jit, static_argnames=("interpret", "block_n", "block_q")
)
def sparse_dot_pallas(
    values: jax.Array,
    indices: jax.Array,
    q: jax.Array,
    *,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> jax.Array:
    """values (N, k) f32, indices (N, k) i32, q (Q, h) f32 -> (Q, N) f32.

    N must be a multiple of block_n and Q of block_q (ops.py pads).
    """
    n, k = values.shape
    nq, h = q.shape
    grid = (nq // block_q, n // block_n)
    return pl.pallas_call(
        _dot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_q, h), lambda qi, i: (qi, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda qi, i: (qi, i)),
        out_shape=jax.ShapeDtypeStruct((nq, n), jnp.float32),
        interpret=interpret,
    )(values, indices, q.astype(jnp.float32))


def _merge_top_n(best_v, best_i, tile_v, base, rounds):
    """Threshold-gated insertion of a tile into the running top-n.

    best_v/best_i: the (BLOCK_Q, n) score-descending (ties: id-ascending)
    buffers; tile_v: the (BLOCK_Q, BLOCK_N) tile scores, whose ids are
    ``base`` + lane.  Each of ``rounds`` rounds takes every row's best
    remaining tile score (first lane on ties) and inserts it at
    p = #(buffer >= score), shifting the tail one lane right; p = n means
    it cannot enter, and that row is left as it is.  Scores come out in
    descending order and the threshold only rises, so once a row's best
    remaining score fails, every later one does: ``rounds`` = the largest
    per-row count of tile scores above the row's n-th best (capped at n)
    is enough.  Returns lax.top_k over the n + BLOCK_N candidates, with
    slots left at -inf carrying the buffer's first id from before the
    merge.  The buffer is padded to whole vregs for the lane rotation.
    """
    bq, n = best_v.shape
    bn = tile_v.shape[1]
    pad = ((0, 0), (0, -n % LANES))
    bv = jnp.pad(best_v, pad, constant_values=_NEG_INF)
    bi = jnp.pad(best_i, pad)
    pos_b = jax.lax.broadcasted_iota(jnp.int32, bv.shape, 1)
    pos_t = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    in_buf = pos_b < n

    def step(_, carry):
        bv, bi, tv = carry
        m = jnp.max(tv, axis=1, keepdims=True)                    # (BQ, 1)
        at = jnp.min(jnp.where(tv == m, pos_t, bn), axis=1, keepdims=True)
        p = jnp.sum(jnp.where(in_buf & (bv >= m), 1, 0), axis=1,
                    keepdims=True)                                 # insert at
        bv = jnp.where(pos_b < p, bv,
                       jnp.where(pos_b == p, m, pltpu.roll(bv, 1, 1)))
        bi = jnp.where(pos_b < p, bi,
                       jnp.where(pos_b == p, base + at, pltpu.roll(bi, 1, 1)))
        return bv, bi, jnp.where(pos_t == at, _NEG_INF, tv)

    bv, bi, _ = jax.lax.fori_loop(0, rounds, step, (bv, bi, tile_v))
    out_v = bv[:, :n]
    return out_v, jnp.where(out_v == _NEG_INF, best_i[:, :1], bi[:, :n])


def _init_best(out_v_ref, out_i_ref, stats_ref=None):
    out_v_ref[...] = jnp.full(out_v_ref.shape, _NEG_INF, jnp.float32)
    out_i_ref[...] = jnp.zeros(out_i_ref.shape, jnp.int32)
    if stats_ref is not None:
        stats_ref[...] = jnp.zeros(stats_ref.shape, jnp.int32)


def _mask_fold_merge(scores, inv, nb, out_v_ref, out_i_ref, *,
                     n, n_valid, block_n, alive=None, stats_ref=None):
    """Shared streaming-top-n tile epilogue: fold the reciprocal candidate
    norms, mask padded rows by id, and merge the tile into the
    VMEM-resident running best buffers (whole-tile skip when nothing beats
    the current n-th best).  ``inv`` is a (1, BLOCK_N) row broadcast over
    the panel, or a per-(query, candidate) (BLOCK_Q, BLOCK_N) tile
    (generation 6, ids local to each query's panel).  ``alive`` — a
    (1, BLOCK_N) f32 1.0/0.0 liveness row from a segmented index's
    deletion mask — rides the padding mask: deleted rows score -inf
    exactly like padding, and a fully-deleted tile takes the same
    whole-tile skip (every score is -inf, so nothing can beat the current
    n-th best).  ``stats_ref`` — a (BLOCK_Q, LANES) int32 block — counts
    the panel's merged tiles in lane 0 and insertion rounds in lane 1."""
    scores = scores * inv                                          # fold 1/‖c‖
    bq, bn = scores.shape
    base = nb * block_n
    ids = base + jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
    keep = ids < n_valid                                           # mask padding
    if alive is not None:
        keep = keep & (alive > 0.0)                                # mask deletions
    scores = jnp.where(keep, scores, _NEG_INF)

    beats = scores > out_v_ref[:, n - 1:n]                         # n-th best

    @pl.when(jnp.any(beats))
    def _merge():
        per_row = jnp.sum(beats.astype(jnp.int32), axis=1)
        rounds = jnp.minimum(jnp.max(per_row), n)
        out_v_ref[...], out_i_ref[...] = _merge_top_n(
            out_v_ref[...], out_i_ref[...], scores, base, rounds)
        if stats_ref is not None:
            lane = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape, 1)
            stats_ref[...] += jnp.where(lane == 0, 1,
                                        jnp.where(lane == 1, rounds, 0))


def _best_outputs(nq, n, block_q, with_stats=False):
    """``pallas_call`` output specs of a fused retrieve: the (Q, n) running
    best scores and ids, each panel's block revisited along the candidate
    axis, and with ``with_stats`` a (Q, LANES) int32 merge-counter block
    (``_mask_fold_merge``; read back by ``_panel_stats``)."""
    specs = [pl.BlockSpec((block_q, n), lambda qi, i: (qi, 0))] * 2
    shapes = [jax.ShapeDtypeStruct((nq, n), jnp.float32),
              jax.ShapeDtypeStruct((nq, n), jnp.int32)]
    if with_stats:
        specs.append(pl.BlockSpec((block_q, LANES), lambda qi, i: (qi, 0)))
        shapes.append(jax.ShapeDtypeStruct((nq, LANES), jnp.int32))
    return {"out_specs": specs, "out_shape": shapes}


def _panel_stats(stats, block_q):
    """(Q, LANES) counter block -> (Q / block_q, 2) int32: per query panel,
    the tiles that ran the merge and the insertion rounds they ran."""
    return stats[::block_q, :2]


def _make_retrieve_kernel(n: int, n_valid: int, block_n: int):
    def kernel(vals_ref, idx_ref, inv_ref, q_ref, out_v_ref, out_i_ref):
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref)

        scores = _score_tile(vals_ref[...], idx_ref[...], q_ref[...])
        _mask_fold_merge(scores, inv_ref[...].T, nb, out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("n", "n_valid", "interpret", "block_n", "block_q")
)
def fused_retrieve_pallas(
    values: jax.Array,
    indices: jax.Array,
    inv_norms: jax.Array,
    q: jax.Array,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> tuple[jax.Array, jax.Array]:
    """Fused score+select: (Q, n) best (norm-folded scores, candidate ids).

    values (N, k) f32, indices (N, k) i32, inv_norms (N, 1) f32 reciprocal
    candidate norms, q (Q, h) f32.  N % block_n == 0, Q % block_q == 0
    (ops.py pads); ``n_valid`` is the true candidate count before padding.
    The (Q, N) score matrix is never materialized.
    """
    N, k = values.shape
    nq, h = q.shape
    grid = (nq // block_q, N // block_n)  # candidate axis innermost
    out_v, out_i = pl.pallas_call(
        _make_retrieve_kernel(n, n_valid, block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_q, h), lambda qi, i: (qi, 0)),
        ],
        **_best_outputs(nq, n, block_q),
        interpret=interpret,
    )(values, indices, inv_norms, q.astype(jnp.float32))
    return out_v, out_i


def _densify_panel(q_vals, q_idx, h: int):
    """(BLOCK_Q, kq) sparse query codes -> (BLOCK_Q, h) dense panel.

    kq comparison-scatter rounds accumulated in l order: duplicate indices
    within a row sum sequentially, exactly like ``sparse.densify``'s
    scatter-add, so downstream scores are bit-identical to the densified
    path.  Runs once per query panel into VMEM scratch — never HBM.
    """
    bq = q_vals.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, h), 1)

    def body(l, acc):
        v = _pick(q_vals, l, 1)                                    # (BQ, 1)
        i = _pick(q_idx, l, 1)                                     # (BQ, 1)
        return acc + jnp.where(col == i, v, 0.0)

    return jax.lax.fori_loop(0, q_vals.shape[1], body,
                             jnp.zeros((bq, h), jnp.float32))


def _sparse_q_refs(rest, with_alive, with_stats):
    """Split a sparse-query kernel's trailing refs: [alive], query values,
    query ids, best scores, best ids, [stats], then the scratch refs."""
    alive_ref = rest[0] if with_alive else None
    qv_ref, qi_ref, out_v_ref, out_i_ref, *rest = rest[with_alive:]
    stats_ref = rest[0] if with_stats else None
    return (alive_ref, qv_ref, qi_ref, out_v_ref, out_i_ref, stats_ref,
            rest[with_stats:])


def _make_retrieve_sparse_q_kernel(n: int, n_valid: int, block_n: int, h: int,
                                   with_alive: bool = False,
                                   with_stats: bool = False):
    def kernel(vals_ref, idx_ref, inv_ref, *rest):
        (alive_ref, qv_ref, qi_ref, out_v_ref, out_i_ref, stats_ref,
         (panel_ref,)) = _sparse_q_refs(rest, with_alive, with_stats)
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref, stats_ref)
            panel_ref[...] = _densify_panel(qv_ref[...], qi_ref[...], h)

        scores = _score_tile(vals_ref[...], idx_ref[...], panel_ref[...])
        _mask_fold_merge(scores, inv_ref[...].T, nb, out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n,
                         alive=None if alive_ref is None else alive_ref[...].T,
                         stats_ref=stats_ref)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "n_valid", "interpret", "block_n", "block_q",
                     "with_stats"),
)
def fused_retrieve_sparse_q_pallas(
    values: jax.Array,
    indices: jax.Array,
    inv_norms: jax.Array,
    q_values: jax.Array,
    q_indices: jax.Array,
    h: int,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    alive: jax.Array | None = None,
    with_stats: bool = False,
) -> tuple[jax.Array, ...]:
    """Sparse-query fused score+select: (Q, n) best (scores, candidate ids).

    values (N, k) f32, indices (N, k) i32, inv_norms (N, 1) f32, q_values
    (Q, kq) f32 + q_indices (Q, kq) i32 sparse query codes over [0, h).
    N % block_n == 0, Q % block_q == 0 (ops.py pads).  The dense query
    panel lives only in a (block_q, h) VMEM scratch, rebuilt per panel;
    query HBM traffic is the (Q, kq) codes — never (Q, h).  ``alive``,
    when given, is an (N, 1) f32 1.0/0.0 deletion mask: dead rows are
    masked to -inf alongside padding, and fully-dead tiles take the
    whole-tile skip.  ``with_stats`` appends a (Q / block_q, 2) int32
    array: per query panel, the tiles that ran the merge and the
    insertion rounds they ran.
    """
    N, k = values.shape
    nq = q_values.shape[0]
    grid = (nq // block_q, N // block_n)  # candidate axis innermost
    kq = q_values.shape[1]
    in_specs = [
        pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
        pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
    ]
    operands = [values, indices, inv_norms,
                q_values.astype(jnp.float32), q_indices]
    if alive is not None:
        in_specs.insert(3, pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)))
        operands.insert(3, alive)
    out = pl.pallas_call(
        _make_retrieve_sparse_q_kernel(n, n_valid, block_n, h,
                                       with_alive=alive is not None,
                                       with_stats=with_stats),
        grid=grid,
        in_specs=in_specs,
        **_best_outputs(nq, n, block_q, with_stats),
        scratch_shapes=[pltpu.VMEM((block_q, h), jnp.float32)],
        interpret=interpret,
    )(*operands)
    if with_stats:
        return out[0], out[1], _panel_stats(out[2], block_q)
    return tuple(out)


def _dequant_tile(q_vals, idx, scales):
    """Quantized candidate tile -> (f32 values, i32 indices), in VMEM.

    q_vals (BLOCK_N, k) int8, idx (BLOCK_N, k) int16/int32, scales
    (BLOCK_N, 1) f32.  The value dequant is the same two ops as
    ``quantize_codes``'s offline dequant (int8→f32 exact, one f32 multiply),
    so downstream scores are bit-identical to scoring pre-dequantized
    values.  int16 indices are the low 16 bits of the original index
    (two's-complement wrapped for h >= 32768): the shared widen recovers
    them exactly.
    """
    return q_vals.astype(jnp.float32) * scales, _widen_idx(idx)


def _make_retrieve_quantized_kernel(n: int, n_valid: int, block_n: int):
    def kernel(qvals_ref, idx_ref, scale_ref, inv_ref, q_ref,
               out_v_ref, out_i_ref):
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref)

        vals, idx = _dequant_tile(qvals_ref[...], idx_ref[...], scale_ref[...])
        scores = _score_tile(vals, idx, q_ref[...])
        _mask_fold_merge(scores, inv_ref[...].T, nb, out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("n", "n_valid", "interpret", "block_n", "block_q")
)
def fused_retrieve_quantized_pallas(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    q: jax.Array,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> tuple[jax.Array, jax.Array]:
    """Quantized-index fused score+select: (Q, n) best (scores, ids).

    q_values (N, k) int8, indices (N, k) int16/int32, scales (N, 1) f32
    per-row dequant scales, inv_norms (N, 1) f32, q (Q, h) f32.  The index
    streams in its quantized dtypes; dequantization happens per tile in
    VMEM (``_dequant_tile``).  Bit-identical to ``fused_retrieve_pallas``
    over the dequantized arrays.
    """
    N, k = q_values.shape
    nq, h = q.shape
    grid = (nq // block_q, N // block_n)  # candidate axis innermost
    out_v, out_i = pl.pallas_call(
        _make_retrieve_quantized_kernel(n, n_valid, block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_q, h), lambda qi, i: (qi, 0)),
        ],
        **_best_outputs(nq, n, block_q),
        interpret=interpret,
    )(q_values, indices, scales, inv_norms, q.astype(jnp.float32))
    return out_v, out_i


def _make_retrieve_quantized_sparse_q_kernel(
    n: int, n_valid: int, block_n: int, h: int, with_alive: bool = False,
    with_stats: bool = False,
):
    def kernel(qvals_ref, idx_ref, scale_ref, inv_ref, *rest):
        (alive_ref, qv_ref, qi_ref, out_v_ref, out_i_ref, stats_ref,
         (panel_ref,)) = _sparse_q_refs(rest, with_alive, with_stats)
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref, stats_ref)
            panel_ref[...] = _densify_panel(qv_ref[...], qi_ref[...], h)

        vals, idx = _dequant_tile(qvals_ref[...], idx_ref[...], scale_ref[...])
        scores = _score_tile(vals, idx, panel_ref[...])
        _mask_fold_merge(scores, inv_ref[...].T, nb, out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n,
                         alive=None if alive_ref is None else alive_ref[...].T,
                         stats_ref=stats_ref)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "n_valid", "interpret", "block_n", "block_q",
                     "with_stats"),
)
def fused_retrieve_quantized_sparse_q_pallas(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    alive: jax.Array | None = None,
    with_stats: bool = False,
) -> tuple[jax.Array, ...]:
    """Quantized candidates × sparse query codes: the full-compression
    serving kernel.  Candidate tiles stream int8/int16 and dequantize in
    VMEM; the (Q, kq) query codes densify into the (block_q, h) VMEM
    scratch panel (generation 3).  Neither an fp32 index nor a dense query
    panel ever exists in HBM.  Bit-identical to
    ``fused_retrieve_sparse_q_pallas`` over the dequantized arrays.
    ``alive``: optional (N, 1) f32 1.0/0.0 deletion mask, ``with_stats``
    the merge counters (see the fp32 sparse-q wrapper for both).
    """
    N, k = q_values.shape
    nq = query_values.shape[0]
    grid = (nq // block_q, N // block_n)  # candidate axis innermost
    kq = query_values.shape[1]
    in_specs = [
        pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
        pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
    ]
    operands = [q_values, indices, scales, inv_norms,
                query_values.astype(jnp.float32), query_indices]
    if alive is not None:
        in_specs.insert(4, pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)))
        operands.insert(4, alive)
    out = pl.pallas_call(
        _make_retrieve_quantized_sparse_q_kernel(n, n_valid, block_n, h,
                                                 with_alive=alive is not None,
                                                 with_stats=with_stats),
        grid=grid,
        in_specs=in_specs,
        **_best_outputs(nq, n, block_q, with_stats),
        scratch_shapes=[pltpu.VMEM((block_q, h), jnp.float32)],
        interpret=interpret,
    )(*operands)
    if with_stats:
        return out[0], out[1], _panel_stats(out[2], block_q)
    return tuple(out)


def _store_quantized_panel(panel, qp_ref, qs_ref):
    """Quantize a dense f32 query panel once per panel (generation 5) into
    scratch: the int8 codes are kept widened to int32, the width Mosaic
    lane-gathers.  Products of two int8 codes are ≤ 127² and k is at most
    a few hundred, so the int32 accumulator in ``_score_tile`` cannot
    overflow; int32 addition is associative, which is what makes the
    kernel bit-identical to the chunked jnp ref's ``jnp.sum``."""
    qi8, qs = _quantize_panel(panel)
    qp_ref[...] = qi8.astype(jnp.int32)
    qs_ref[...] = qs


def _make_retrieve_quantized_mxu_kernel(n: int, n_valid: int, block_n: int):
    def kernel(qvals_ref, idx_ref, scale_ref, inv_ref, q_ref,
               out_v_ref, out_i_ref, qp_ref, qs_ref):
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref)
            _store_quantized_panel(q_ref[...], qp_ref, qs_ref)

        acc = _score_tile(qvals_ref[...], _widen_idx(idx_ref[...]),
                          qp_ref[...])
        scores = acc.astype(jnp.float32) * qs_ref[...]             # fold q scale
        # candidate-side rescale (row dequant scale × reciprocal norm)
        # rides the existing inv-norm fold in the shared epilogue
        _mask_fold_merge(scores, (scale_ref[...] * inv_ref[...]).T, nb,
                         out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("n", "n_valid", "interpret", "block_n", "block_q")
)
def fused_retrieve_quantized_mxu_pallas(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    q: jax.Array,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> tuple[jax.Array, jax.Array]:
    """Int8-scoring fused score+select (generation 5, APPROXIMATE).

    Same operands as ``fused_retrieve_quantized_pallas``, but the tile is
    never dequantized: the f32 query panel quantizes once per panel into
    int8 VMEM scratch, scoring runs int8×int8 → int32, and one f32
    rescale folds into the merge.  Bit-identical to
    ``retrieve_quantized_mxu_ref``; quality vs the exact quantized path
    is measured (``repro.core.eval``), not exact.
    """
    N, k = q_values.shape
    nq, h = q.shape
    grid = (nq // block_q, N // block_n)  # candidate axis innermost
    out_v, out_i = pl.pallas_call(
        _make_retrieve_quantized_mxu_kernel(n, n_valid, block_n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
            pl.BlockSpec((block_q, h), lambda qi, i: (qi, 0)),
        ],
        **_best_outputs(nq, n, block_q),
        scratch_shapes=[
            pltpu.VMEM((block_q, h), jnp.int32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_values, indices, scales, inv_norms, q.astype(jnp.float32))
    return out_v, out_i


def _make_retrieve_quantized_mxu_sparse_q_kernel(
    n: int, n_valid: int, block_n: int, h: int, with_alive: bool = False
):
    def kernel(qvals_ref, idx_ref, scale_ref, inv_ref, *rest):
        (alive_ref, qv_ref, qi_ref, out_v_ref, out_i_ref, _,
         (qp_ref, qs_ref)) = _sparse_q_refs(rest, with_alive, False)
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref)
            # densify the code panel (generation 3's scatter) and quantize
            # it in one go — the f32 panel is a temporary value, only the
            # quantized panel + scales persist in scratch
            _store_quantized_panel(
                _densify_panel(qv_ref[...], qi_ref[...], h), qp_ref, qs_ref)

        acc = _score_tile(qvals_ref[...], _widen_idx(idx_ref[...]),
                          qp_ref[...])
        scores = acc.astype(jnp.float32) * qs_ref[...]
        _mask_fold_merge(scores, (scale_ref[...] * inv_ref[...]).T, nb,
                         out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n,
                         alive=None if alive_ref is None else alive_ref[...].T)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "n_valid", "interpret", "block_n", "block_q"),
)
def fused_retrieve_quantized_mxu_sparse_q_pallas(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
    alive: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Int8-scoring × sparse query codes (generation 5, APPROXIMATE): the
    full-compression serving kernel with no dequant anywhere.  The (Q, kq)
    codes densify into a VMEM panel, quantize per row into int8 scratch,
    and score the int8 candidate stream with exact int32 accumulation.
    Bit-identical to ``retrieve_quantized_mxu_sparse_q_ref``.
    ``alive``: optional (N, 1) f32 1.0/0.0 deletion mask (see the fp32
    sparse-q wrapper).
    """
    N, k = q_values.shape
    nq = query_values.shape[0]
    grid = (nq // block_q, N // block_n)  # candidate axis innermost
    kq = query_values.shape[1]
    in_specs = [
        pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, k), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)),
        pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
        pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
    ]
    operands = [q_values, indices, scales, inv_norms,
                query_values.astype(jnp.float32), query_indices]
    if alive is not None:
        in_specs.insert(4, pl.BlockSpec((block_n, 1), lambda qi, i: (i, 0)))
        operands.insert(4, alive)
    out_v, out_i = pl.pallas_call(
        _make_retrieve_quantized_mxu_sparse_q_kernel(
            n, n_valid, block_n, h, with_alive=alive is not None
        ),
        grid=grid,
        in_specs=in_specs,
        **_best_outputs(nq, n, block_q),
        scratch_shapes=[
            pltpu.VMEM((block_q, h), jnp.int32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out_v, out_i


# --------------------------------------------------------------------------
# Generation 6: gather-aware re-rank (batched two-stage stage 2)
# --------------------------------------------------------------------------
# ``_score_tile`` takes the (BLOCK_Q, BLOCK_N, k) bricks as they are: round
# j's (BLOCK_Q, BLOCK_N) index slab gathers each query row's OWN panel
# lanes, with the same k-round FMA order as the shared tile, so each query
# row is bit-identical to the per-query kernel on its gathered sub-tile.
# ``_mask_fold_merge`` takes the per-(query, candidate) (BLOCK_Q, BLOCK_N)
# rescale tile as is, and masks padding against the LOCAL candidate
# position (ids are positions in [0, B), not catalog rows).

def _make_retrieve_gathered_sparse_q_kernel(
    n: int, n_valid: int, block_n: int, h: int
):
    def kernel(vals_ref, idx_ref, inv_ref, qv_ref, qi_ref,
               out_v_ref, out_i_ref, panel_ref):
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref)
            panel_ref[...] = _densify_panel(qv_ref[...], qi_ref[...], h)

        scores = _score_tile(vals_ref[...], idx_ref[...], panel_ref[...])
        _mask_fold_merge(scores, inv_ref[...], nb, out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "n_valid", "interpret", "block_n", "block_q"),
)
def fused_retrieve_gathered_sparse_q_pallas(
    values: jax.Array,
    indices: jax.Array,
    inv_norms: jax.Array,
    q_values: jax.Array,
    q_indices: jax.Array,
    h: int,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> tuple[jax.Array, jax.Array]:
    """Gathered sparse-query fused score+select (generation 6, fp32).

    values (Q, B, k) f32 per-query candidate panels, indices (Q, B, k)
    i32, inv_norms (Q, B) f32, q_values/q_indices (Q, kq) sparse query
    codes over [0, h).  B % block_n == 0, Q % block_q == 0 (ops.py pads);
    ``n_valid`` is the true per-query candidate count before padding.
    Returns (Q, n) best (norm-folded scores, LOCAL candidate positions in
    [0, B)).  Bit-identical per query to ``fused_retrieve_sparse_q_pallas``
    over the gathered sub-arrays.
    """
    nq, B, k = values.shape
    grid = (nq // block_q, B // block_n)  # candidate axis innermost
    kq = q_values.shape[1]
    out_v, out_i = pl.pallas_call(
        _make_retrieve_gathered_sparse_q_kernel(n, n_valid, block_n, h),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, block_n, k), lambda qi, i: (qi, i, 0)),
            pl.BlockSpec((block_q, block_n, k), lambda qi, i: (qi, i, 0)),
            pl.BlockSpec((block_q, block_n), lambda qi, i: (qi, i)),
            pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
            pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
        ],
        **_best_outputs(nq, n, block_q),
        scratch_shapes=[pltpu.VMEM((block_q, h), jnp.float32)],
        interpret=interpret,
    )(values, indices, inv_norms,
      q_values.astype(jnp.float32), q_indices)
    return out_v, out_i


def _dequant_tile_gathered(q_vals, idx, scales):
    """Quantized (BLOCK_Q, BLOCK_N, k) brick -> (f32 values, i32 indices).

    Same two dequant ops per element as ``_dequant_tile`` with the scale
    column now a per-(query, candidate) (BLOCK_Q, BLOCK_N) tile.
    """
    return q_vals.astype(jnp.float32) * scales[..., None], _widen_idx(idx)


def _make_retrieve_gathered_quantized_sparse_q_kernel(
    n: int, n_valid: int, block_n: int, h: int
):
    def kernel(qvals_ref, idx_ref, scale_ref, inv_ref, qv_ref, qi_ref,
               out_v_ref, out_i_ref, panel_ref):
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref)
            panel_ref[...] = _densify_panel(qv_ref[...], qi_ref[...], h)

        vals, idx = _dequant_tile_gathered(
            qvals_ref[...], idx_ref[...], scale_ref[...]
        )
        scores = _score_tile(vals, idx, panel_ref[...])
        _mask_fold_merge(scores, inv_ref[...], nb, out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "n_valid", "interpret", "block_n", "block_q"),
)
def fused_retrieve_gathered_quantized_sparse_q_pallas(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> tuple[jax.Array, jax.Array]:
    """Gathered quantized × sparse-query fused score+select (generation 6).

    q_values (Q, B, k) int8, indices (Q, B, k) int16/int32, scales and
    inv_norms (Q, B) f32, query codes (Q, kq).  The per-query candidate
    panels stream in their quantized storage dtypes and dequantize per
    brick in VMEM.  Bit-identical per query to
    ``fused_retrieve_quantized_sparse_q_pallas`` over the gathered
    sub-arrays.
    """
    nq, B, k = q_values.shape
    grid = (nq // block_q, B // block_n)  # candidate axis innermost
    kq = query_values.shape[1]
    out_v, out_i = pl.pallas_call(
        _make_retrieve_gathered_quantized_sparse_q_kernel(
            n, n_valid, block_n, h
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, block_n, k), lambda qi, i: (qi, i, 0)),
            pl.BlockSpec((block_q, block_n, k), lambda qi, i: (qi, i, 0)),
            pl.BlockSpec((block_q, block_n), lambda qi, i: (qi, i)),
            pl.BlockSpec((block_q, block_n), lambda qi, i: (qi, i)),
            pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
            pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
        ],
        **_best_outputs(nq, n, block_q),
        scratch_shapes=[pltpu.VMEM((block_q, h), jnp.float32)],
        interpret=interpret,
    )(q_values, indices, scales, inv_norms,
      query_values.astype(jnp.float32), query_indices)
    return out_v, out_i


def _make_retrieve_gathered_quantized_mxu_sparse_q_kernel(
    n: int, n_valid: int, block_n: int, h: int
):
    def kernel(qvals_ref, idx_ref, scale_ref, inv_ref, qv_ref, qi_ref,
               out_v_ref, out_i_ref, qp_ref, qs_ref):
        nb = pl.program_id(1)

        @pl.when(nb == 0)
        def _init():
            _init_best(out_v_ref, out_i_ref)
            _store_quantized_panel(
                _densify_panel(qv_ref[...], qi_ref[...], h), qp_ref, qs_ref)

        acc = _score_tile(qvals_ref[...], _widen_idx(idx_ref[...]),
                          qp_ref[...])
        scores = acc.astype(jnp.float32) * qs_ref[...]             # fold q scale
        _mask_fold_merge(scores, scale_ref[...] * inv_ref[...], nb,
                         out_v_ref, out_i_ref,
                         n=n, n_valid=n_valid, block_n=block_n)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("h", "n", "n_valid", "interpret", "block_n", "block_q"),
)
def fused_retrieve_gathered_quantized_mxu_sparse_q_pallas(
    q_values: jax.Array,
    indices: jax.Array,
    scales: jax.Array,
    inv_norms: jax.Array,
    query_values: jax.Array,
    query_indices: jax.Array,
    h: int,
    *,
    n: int,
    n_valid: int,
    interpret: bool = False,
    block_n: int = BLOCK_N,
    block_q: int = BLOCK_Q,
) -> tuple[jax.Array, jax.Array]:
    """Gathered int8-scoring × sparse-query fused score+select
    (generation 6 × 5, APPROXIMATE vs exact).  Per-query int8 candidate
    bricks score against the once-per-panel quantized query scratch with
    exact int32 accumulation; one f32 rescale — (acc·q_scale) ·
    (row_scale·inv_norm), the scale/norm factors now per-(query,
    candidate) tiles — folds into the merge.  Bit-identical per query to
    ``fused_retrieve_quantized_mxu_sparse_q_pallas`` over the gathered
    sub-arrays, and to ``retrieve_gathered_quantized_mxu_sparse_q_ref``.
    """
    nq, B, k = q_values.shape
    grid = (nq // block_q, B // block_n)  # candidate axis innermost
    kq = query_values.shape[1]
    out_v, out_i = pl.pallas_call(
        _make_retrieve_gathered_quantized_mxu_sparse_q_kernel(
            n, n_valid, block_n, h
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, block_n, k), lambda qi, i: (qi, i, 0)),
            pl.BlockSpec((block_q, block_n, k), lambda qi, i: (qi, i, 0)),
            pl.BlockSpec((block_q, block_n), lambda qi, i: (qi, i)),
            pl.BlockSpec((block_q, block_n), lambda qi, i: (qi, i)),
            pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
            pl.BlockSpec((block_q, kq), lambda qi, i: (qi, 0)),
        ],
        **_best_outputs(nq, n, block_q),
        scratch_shapes=[
            pltpu.VMEM((block_q, h), jnp.int32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_values, indices, scales, inv_norms,
      query_values.astype(jnp.float32), query_indices)
    return out_v, out_i
