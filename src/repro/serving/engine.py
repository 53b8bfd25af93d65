"""End-to-end dense-query serving engine (ISSUE 3 tentpole).

``RetrievalEngine`` is the serving API as an object with a lifecycle: it
owns ``(params, index, mode, use_kernel, mesh)`` at construction and
exposes

    engine.retrieve_dense(x, n)   # dense embeddings in, RetrievalResponse out

with **no SparseCodes→dense-query round-trip through HBM**.  On the TPU
kernel path a request flows

    fused_encode  →  fused_retrieve_sparse_q

so only the (Q, k) query codes and the (Q, n) results ever touch HBM: the
encoder's abs-top-k epilogue stays in VMEM (no (B, h) pre-activations) and
the retrieval kernel densifies the query panel into VMEM scratch instead
of reading a dense (Q, h) matrix.  The chunked-jnp path mirrors the same
contract on CPU (``sae.encode`` + ``retrieve_sparse_q_ref``) and is
bit-identical to the composed ``encode()`` + ``retrieve()`` pipeline.

The per-request data flow is factored into two functional pieces that the
older call-sites (``core.retrieval.retrieve``,
``distributed.retrieve.distributed_retrieve``) now wrap:

``prep_query(index, q, mode, params)``
    -> ``PreppedQuery``: the mode's query representation + ‖q‖.  Sparse
    mode keeps the (Q, k) codes as-is; reconstructed mode folds the
    kernel-trick query z = W_decᵀ(W_dec s_q) (dense by construction) into
    the prep, with ‖q‖ = ‖W_dec s_q‖.

``retrieve_prepped(index, pq, n, use_fused=...)``
    single-device streaming score+select over either representation.

Distributed serving replicates the *prepped* query into the
candidate-sharded shard_map (``distributed.retrieve
.distributed_retrieve_prepped``) — for sparse mode that is the (Q, k)
codes, an h/(2k)× smaller replication payload than the dense panel the
previous generation broadcast.
"""
from __future__ import annotations

import copy
import warnings
from collections.abc import Mapping
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from repro import compat
from repro.compat import P
from repro.core import sae
from repro.core.quantized_codes import QuantizedCodes
from repro.core.retrieval import (
    NORM_EPS, index_codes_f32, kernel_path, query_norm, two_stage_retrieve,
)
from repro.core.segments import SegmentedIndex
from repro.core.types import SparseCodes
from repro.errors import EngineConfigError, InvalidQueryError
from repro.kernels.fused_encode import fused_encode
from repro.kernels.sparse_dot.kernel import BLOCK_Q
from repro.serving.config import (  # noqa: F401 — re-exported API
    PRECISIONS,
    EngineConfig,
    check_precision,
)
from repro.serving.response import RetrievalResponse, ServingStatus
from repro.kernels.sparse_dot import (
    fused_retrieve,
    fused_retrieve_gathered_quantized_mxu_sparse_q,
    fused_retrieve_gathered_quantized_sparse_q,
    fused_retrieve_gathered_sparse_q,
    fused_retrieve_quantized,
    fused_retrieve_quantized_mxu,
    fused_retrieve_quantized_mxu_sparse_q,
    fused_retrieve_quantized_sparse_q,
    fused_retrieve_sparse_q,
    retrieve_gathered_quantized_mxu_sparse_q_ref,
    retrieve_gathered_quantized_sparse_q_ref,
    retrieve_gathered_sparse_q_ref,
    retrieve_quantized_mxu_ref,
    retrieve_quantized_mxu_sparse_q_ref,
    retrieve_quantized_ref,
    retrieve_quantized_sparse_q_ref,
    retrieve_ref,
    retrieve_sparse_q_ref,
)

def resolve_stage1(stage1: str) -> str:
    """The stage-1 implementation a ``stage1`` knob actually runs
    ("auto" resolves to the device union)."""
    return "device" if stage1 == "auto" else stage1


def path_name(engine: "RetrievalEngine") -> str:
    """The canonical serving-path name of an engine's configuration —
    what a healthy ``ServingStatus.path`` reports and what the guard
    ladder's rung names are built from."""
    quantized = isinstance(engine.index.codes, QuantizedCodes)
    fmt = ("int8" if engine.precision == "int8"
           else "quantized" if quantized else "fp32")
    backend = "kernel" if engine.use_fused else "ref"
    sharded = "-sharded" if engine.mesh is not None else ""
    prefix = (f"two-stage-{resolve_stage1(engine.stage1)}-"
              if engine.stage == "two_stage" else "")
    return f"{prefix}{fmt}-{backend}{sharded}"


def validate_topn(n, n_candidates: int) -> int:
    """Admission check for the ``n`` of a top-n request (typed, named)."""
    if isinstance(n, bool) or not isinstance(n, (int, np.integer)):
        raise InvalidQueryError(
            f"n: expected a Python int, got {type(n).__name__} ({n!r})"
        )
    if n < 1:
        raise InvalidQueryError(f"n: top-n must be >= 1, got {n}")
    if n > n_candidates:
        raise InvalidQueryError(
            f"n: top-n {n} exceeds candidate count {n_candidates}"
        )
    return int(n)


def validate_dense_query(
    x, *, d: Optional[int] = None, name: str = "x"
):
    """Trace-safe admission checks for a dense query batch: array-ness,
    rank, embedding dim, floating dtype.  Every failure is an
    ``InvalidQueryError`` naming the offending argument and the expected
    vs actual shape/dtype.  Value checks (finiteness) are the guard
    layer's job — they need concrete bytes and never belong under jit.
    """
    if not hasattr(x, "shape") or not hasattr(x, "dtype"):
        raise InvalidQueryError(
            f"{name}: expected an array of dense embeddings, got "
            f"{type(x).__name__}"
        )
    if x.ndim not in (1, 2):
        raise InvalidQueryError(
            f"{name}: expected shape (d,) or (Q, d), got rank-{x.ndim} "
            f"shape {tuple(x.shape)}"
        )
    if d is not None and x.shape[-1] != d:
        raise InvalidQueryError(
            f"{name}: embedding dim mismatch — expected last axis {d} "
            f"(the SAE input dim), got {x.shape[-1]} "
            f"(shape {tuple(x.shape)})"
        )
    if not jnp.issubdtype(x.dtype, jnp.floating):
        raise InvalidQueryError(
            f"{name}: expected a floating dtype, got {x.dtype}"
        )
    return x


def validate_query_codes(
    q: SparseCodes, *, h: int, name: str = "q"
) -> SparseCodes:
    """Trace-safe admission checks for query ``SparseCodes``: matching
    values/indices shapes, integer indices, code dim == index dim."""
    if tuple(q.values.shape) != tuple(q.indices.shape):
        raise InvalidQueryError(
            f"{name}: values shape {tuple(q.values.shape)} != indices "
            f"shape {tuple(q.indices.shape)} (fixed-k codes must pair "
            "one index per value)"
        )
    if q.values.ndim not in (1, 2):
        raise InvalidQueryError(
            f"{name}: expected code shape (k,) or (Q, k), got rank-"
            f"{q.values.ndim} shape {tuple(q.values.shape)}"
        )
    if not jnp.issubdtype(q.indices.dtype, jnp.integer):
        raise InvalidQueryError(
            f"{name}: indices must be an integer dtype, got "
            f"{q.indices.dtype}"
        )
    # dim rides the SparseCodes pytree as a leaf, so a jit'd producer
    # (fused_encode) hands it back traced — the check only applies where
    # dim is still concrete (every external entry point)
    try:
        dim = int(q.dim)
    except jax.errors.ConcretizationTypeError:
        dim = h
    if dim != h:
        raise InvalidQueryError(
            f"{name}: code dim mismatch — query codes address a "
            f"{dim}-wide latent space, index stores {h}"
        )
    return q


class PreppedQuery(NamedTuple):
    """A query batch in the representation its retrieval mode scores with.

    Exactly one of (``values`` + ``indices``) or ``dense`` is set:
    sparse mode carries the (Q?, k) codes straight through (the sparse-query
    kernel densifies in VMEM); reconstructed mode carries the dense
    z = W_decᵀ(W_dec s_q) — dense by construction, same shape economics as
    the kernel-trick identity.  ``norm`` is the per-query cosine
    denominator ‖q‖ (sparse: ‖s_q‖; reconstructed: ‖W_dec s_q‖).
    """

    values: Optional[jax.Array]
    indices: Optional[jax.Array]
    dense: Optional[jax.Array]
    norm: jax.Array

    @property
    def is_sparse(self) -> bool:
        return self.values is not None


def mode_inv_norms(index, mode: str) -> jax.Array:
    """The index's reciprocal candidate norms for a scoring mode."""
    if mode == "sparse":
        inv = index.inv_sparse_norms
        if inv is None:
            inv = 1.0 / jnp.maximum(index.sparse_norms, NORM_EPS)
        return inv
    if mode == "reconstructed":
        if index.recon_norms is None:
            raise EngineConfigError(
                "index built without params; recon norms missing"
            )
        inv = index.inv_recon_norms
        if inv is None:
            inv = 1.0 / jnp.maximum(index.recon_norms, NORM_EPS)
        return inv
    raise EngineConfigError(f"unknown retrieval mode: {mode!r}")


def prep_query(
    index,
    q: SparseCodes,
    mode: str,
    params: Optional[sae.Params] = None,
) -> PreppedQuery:
    """Query codes -> the mode's scoring representation (see module doc)."""
    if mode == "sparse":
        return PreppedQuery(
            values=q.values, indices=q.indices, dense=None,
            norm=query_norm(q.values),
        )
    if mode == "reconstructed":
        if params is None:
            raise EngineConfigError("mode='reconstructed' requires SAE params")
        x_hat_q = sae.decode(params, q)                    # (Q?, d)
        z = x_hat_q @ params["w_dec"].T                    # (Q?, h) == K s_q
        return PreppedQuery(
            values=None, indices=None, dense=z,
            norm=query_norm(x_hat_q),
        )
    raise EngineConfigError(f"unknown retrieval mode: {mode!r}")


def select_retrieve_fn(
    *, sparse_query: bool, quantized: bool, int8_scoring: bool,
    use_fused: bool,
):
    """THE kernel-generation dispatch table, in one place.

    Maps (query representation, index format, scoring precision, backend)
    to the streaming retrieve callable.  ``retrieve_prepped``, the
    distributed shard body, and the partial-merge recovery path all select
    through here, so the three serving paths cannot drift onto different
    generations for the same configuration.
    """
    if int8_scoring:
        if sparse_query:
            return (fused_retrieve_quantized_mxu_sparse_q if use_fused
                    else retrieve_quantized_mxu_sparse_q_ref)
        return (fused_retrieve_quantized_mxu if use_fused
                else retrieve_quantized_mxu_ref)
    if quantized:
        if sparse_query:
            return (fused_retrieve_quantized_sparse_q if use_fused
                    else retrieve_quantized_sparse_q_ref)
        return fused_retrieve_quantized if use_fused else retrieve_quantized_ref
    if sparse_query:
        return fused_retrieve_sparse_q if use_fused else retrieve_sparse_q_ref
    return fused_retrieve if use_fused else retrieve_ref


def select_gathered_retrieve_fn(
    *, quantized: bool, int8_scoring: bool, use_fused: bool,
):
    """Generation-6 dispatch: the gather-aware re-rank for batched
    two-stage stage 2.  Candidate arrays carry a leading query axis
    ((Q, B, k) panels, (Q, B) norms/scales) and ids come back as LOCAL
    panel positions.  Always sparse-query — two-stage retrieval is
    sparse-mode only — so the table is the sparse-q column of
    ``select_retrieve_fn`` with the gathered twins substituted.  Kept
    beside it so the two tables cannot drift."""
    if int8_scoring:
        return (fused_retrieve_gathered_quantized_mxu_sparse_q if use_fused
                else retrieve_gathered_quantized_mxu_sparse_q_ref)
    if quantized:
        return (fused_retrieve_gathered_quantized_sparse_q if use_fused
                else retrieve_gathered_quantized_sparse_q_ref)
    return (fused_retrieve_gathered_sparse_q if use_fused
            else retrieve_gathered_sparse_q_ref)


def retrieve_prepped(
    index,
    pq: PreppedQuery,
    n: int,
    *,
    use_fused: bool,
    inv_norms: Optional[jax.Array] = None,
    precision: str = "exact",
) -> tuple[jax.Array, jax.Array]:
    """Single-device streaming score+select over a prepped query batch.

    Dispatches the sparse-query kernel/ref pair when ``pq`` carries codes,
    the dense-query pair when it carries z; folds ‖q‖ on the (Q, n) panel
    only.  Bit-identical to densifying first (the kernels guarantee it).
    The candidate inv norms default to the mode the prepped representation
    implies (codes → sparse-space, dense z → reconstructed-space).

    A ``QuantizedIndex`` routes to the quantized kernel/ref generation:
    the candidate side streams int8/int16 + per-row scales and dequantizes
    in VMEM (kernel) or per block (ref) — bit-identical to serving the
    dequantized index, with the index never materialized in fp32.

    ``precision="int8"`` (QuantizedIndex only) instead routes to
    generation 5: candidate tiles are scored in int8 (query panel
    quantized in VMEM, int32 accumulation, one f32 rescale in the merge)
    — an APPROXIMATE path whose quality vs ``"exact"`` is measured by
    ``repro.core.eval`` and gated on recall, not bit-identity.  Kernel
    and ref remain bit-identical to each other on this path too.
    """
    check_precision(index, precision)
    if inv_norms is None:
        inv_norms = mode_inv_norms(
            index, "sparse" if pq.is_sparse else "reconstructed"
        )
    squeeze = pq.norm.ndim == 0
    quantized = isinstance(index.codes, QuantizedCodes)
    int8_scoring = precision == "int8"
    if quantized:
        cand = (index.codes.q_values, index.codes.indices, index.codes.scales)
    else:
        cand = (index.codes.values, index.codes.indices)
    fn = select_retrieve_fn(
        sparse_query=pq.is_sparse, quantized=quantized,
        int8_scoring=int8_scoring, use_fused=use_fused,
    )
    if pq.is_sparse:
        qv = pq.values[None] if squeeze else pq.values
        qi = pq.indices[None] if squeeze else pq.indices
        vals, ids = fn(*cand, inv_norms, qv, qi, index.codes.dim, n=n)
    else:
        qd = pq.dense[None] if squeeze else pq.dense
        vals, ids = fn(*cand, inv_norms, qd, n=n)
    norm = pq.norm[None] if squeeze else pq.norm
    scores = vals / jnp.maximum(norm[..., None], NORM_EPS)
    if squeeze:
        scores, ids = scores[0], ids[0]
    return scores, ids


_LEGACY_ENGINE_KWARGS = frozenset((
    "mode", "use_kernel", "mesh", "shard_axis", "k", "precision",
    "stage", "stage1", "candidate_fraction", "inverted_cap",
))


def _looks_like_index(obj) -> bool:
    return isinstance(obj, SegmentedIndex) or hasattr(obj, "codes")


def _looks_like_params(obj) -> bool:
    return isinstance(obj, Mapping) and "w_enc" in obj


def _normalize_ctor_order(index, params):
    """Accept both ``RetrievalEngine(index, params)`` (primary) and the
    legacy ``RetrievalEngine(params, index)`` order.  The two argument
    kinds are structurally unambiguous — an index carries ``.codes`` (or
    is a ``SegmentedIndex``), params are a mapping with ``"w_enc"`` — so
    detection is type-based, and the legacy order earns a
    ``DeprecationWarning``."""
    if _looks_like_index(index) and (params is None
                                     or _looks_like_params(params)):
        return index, params
    if _looks_like_params(index) or _looks_like_index(params):
        warnings.warn(
            "RetrievalEngine(params, index) argument order is deprecated; "
            "use RetrievalEngine(index, params, config=...)",
            DeprecationWarning, stacklevel=3,
        )
        return params, index
    raise EngineConfigError(
        "RetrievalEngine(index, params): could not identify an index "
        f"(needs .codes or SegmentedIndex) in ({type(index).__name__}, "
        f"{type(params).__name__})"
    )


class RetrievalEngine:
    """One object owns the serving lifecycle: index, params, and one
    ``EngineConfig`` naming every knob (mode, backend, precision, staging,
    mesh).  Construct once — ``RetrievalEngine(index, params,
    config=EngineConfig(...))`` — then serve ``retrieve_dense(x, n)``: raw
    dense embeddings in, a ``RetrievalResponse`` (top-n cosine scores,
    candidate ids, ``ServingStatus``, latency split) out.  The legacy
    ``RetrievalEngine(params, index, mode=..., ...)`` spelling still
    works through a shim that emits ``DeprecationWarning``.

    ``use_kernel``: "auto" (fused Pallas chain on TPU, chunked jnp
    elsewhere) | True | False — same switch as ``core.retrieve``.
    ``index``: a ``SparseIndex`` or a ``QuantizedIndex``
    (``build_index(..., quantize=True)``) — the quantized format is served
    AS-IS: its int8/int16 arrays are what lives in HBM (and what a mesh
    shards), dequantized tile-by-tile in VMEM by the quantized kernel
    generation, bit-identical to serving the dequantized index.
    ``mesh``: a mesh with a ``shard_axis`` axis routes every request
    through candidate-sharded distributed retrieval, with the prepped
    query replicated (for sparse mode: just the (Q, k) codes).
    ``precision``: ``"exact"`` (default; bit-identical to the fp32 path)
    or ``"int8"`` (generation 5's approximate int8-scoring fast path —
    QuantizedIndex only, quality gated on recall via ``repro.core.eval``).
    ``stage``: ``"single"`` (default; every request scores the full
    catalog) or ``"two_stage"`` — stage 1 unions the query's posting
    lists from an inverted index built at engine construction into a
    bounded candidate set (``candidate_fraction`` of the catalog,
    posting lists capped at ``inverted_cap``), stage 2 gathers every
    query's candidate panel in one batched device gather and runs ONE
    gather-aware fused re-rank over the whole (Q, budget) panel
    (``core.retrieval.two_stage_retrieve``, generation-6 kernels).
    Sub-linear in catalog size and APPROXIMATE (recall-gated in
    benchmarks); sparse mode, unsharded only — sharding composes with
    single-stage instead.
    ``stage1``: ``"auto"``/``"device"`` (default; the batched jitted
    ``device_candidate_union`` — no per-query host work) or ``"host"``
    (the numpy ``candidate_union`` parity oracle — bit-identical rows,
    and the guard ladder's fallback between device two-stage and
    single-stage).

    ``retrieve_dense`` jit-compiles the whole request (encode → score →
    select) once per distinct ``n``, with the index and params as
    arguments, and caches the executable, so steady-state serving is a
    single dispatch.  (Two-stage requests compile two cached jits —
    encode and the batched stage-2 re-rank — with the candidate union
    between them.)
    """

    def __init__(self, index=None, params: Optional[sae.Params] = None,
                 *, config: Optional[EngineConfig] = None, **legacy):
        index, params = _normalize_ctor_order(index, params)
        if legacy:
            unknown = set(legacy) - _LEGACY_ENGINE_KWARGS
            if unknown:
                raise TypeError(
                    "RetrievalEngine got unexpected keyword argument(s) "
                    f"{sorted(unknown)}"
                )
            if config is not None:
                raise EngineConfigError(
                    "pass either config=EngineConfig(...) or the legacy "
                    f"keyword knobs {sorted(legacy)}, not both"
                )
            warnings.warn(
                "RetrievalEngine(..., mode=/use_kernel=/...) keyword knobs "
                "are deprecated; pass config=EngineConfig(...) instead",
                DeprecationWarning, stacklevel=2,
            )
            config = EngineConfig(**legacy)
        cfg = EngineConfig() if config is None else config
        cfg.validate(index, params)

        self.config = cfg
        self.segments: Optional[SegmentedIndex] = None
        if isinstance(index, SegmentedIndex):
            self.segments = index
            index = index.base
        self.params = params
        self.index = index
        self.mode = cfg.mode
        self.use_kernel = cfg.use_kernel
        self.use_fused = kernel_path(cfg.use_kernel)
        self.mesh = cfg.mesh
        self.shard_axis = cfg.shard_axis
        self.k = index.codes.k if cfg.k is None else cfg.k
        self.precision = cfg.precision
        self.stage = cfg.stage
        self.stage1 = cfg.stage1
        self.candidate_fraction = cfg.candidate_fraction
        self.inverted_cap = cfg.inverted_cap
        self._inv_norms = mode_inv_norms(index, cfg.mode)
        self._serve_cache: dict[int, callable] = {}
        self.counters = {"serve_traces": 0}
        self._placed = None  # mesh engines: serve args placed on the mesh
        self.inverted = None
        if cfg.stage == "two_stage":
            from repro.core.inverted_index import build_inverted_index

            self.inverted = build_inverted_index(
                index_codes_f32(index), cap=cfg.inverted_cap
            )
            self._two_stage_cache: dict = {}

    # ------------------------------------------------------------- mutation
    def apply_update(self, op: str, *, codes=None, ids=None):
        """Apply one catalog mutation to a segmented engine, atomically.

        ``op``: ``"add"`` (requires ``codes`` — fp32 (m, k) SparseCodes —
        and ``ids``), ``"delete"`` (requires ``ids``), or ``"compact"``.
        The lifecycle ops are functional, so the engine swaps to the new
        ``SegmentedIndex`` only after the op succeeded — a rejected
        mutation (``SegmentMutationError``) leaves serving untouched.
        Returns the new ``SegmentedIndex``.

        No jit cache is invalidated: the serving path deliberately never
        bakes segment arrays into a per-engine jit (see
        ``retrieve_dense``), and the module-level retrieve jits key on
        array shapes — an add/compact that changes the delta shape
        retraces exactly those, a delete (same shapes, new mask) reuses
        everything.
        """
        if self.segments is None:
            raise EngineConfigError(
                "apply_update requires an engine constructed over a "
                "SegmentedIndex (core.segments); this engine serves an "
                f"immutable {type(self.index).__name__}"
            )
        if op == "add":
            if codes is None or ids is None:
                raise EngineConfigError("op='add' requires codes and ids")
            seg = self.segments.add_items(codes, ids)
        elif op == "delete":
            if ids is None:
                raise EngineConfigError("op='delete' requires ids")
            seg = self.segments.delete_items(ids)
        elif op == "compact":
            seg = self.segments.compact()
        else:
            raise EngineConfigError(
                f"unknown update op {op!r} "
                "(expected 'add', 'delete' or 'compact')"
            )
        self.segments = seg
        self.index = seg.base
        self._inv_norms = mode_inv_norms(seg.base, self.mode)
        return seg

    # ---------------------------------------------------------- request flow
    def _serve_leaves(self):
        """The arrays the whole-request jit reads — index, inv norms,
        params — as (array leaves, rebuild).  They are jit ARGUMENTS: a
        captured catalog would be copied into the compiled program as a
        constant."""
        leaves, treedef = jax.tree.flatten(
            (self.index, self._inv_norms, self.params))
        is_arr = [isinstance(v, jax.Array) for v in leaves]

        def rebuild(arrays):
            it = iter(arrays)
            return jax.tree.unflatten(
                treedef, [next(it) if a else v for a, v in zip(is_arr, leaves)])

        return [v for v, a in zip(leaves, is_arr) if a], rebuild

    def _serve_shardings(self, arrays):
        """Where a mesh engine keeps ``_serve_leaves``' arrays: index rows
        and their inv norms split along the candidate axis when they
        divide evenly, everything else replicated."""
        from repro.distributed.retrieve import mesh_shard_count

        n_rows = self.index.codes.n
        split = n_rows % mesh_shard_count(self.mesh, self.shard_axis) == 0
        n_cand = sum(isinstance(v, jax.Array) for v in
                     jax.tree.leaves((self.index, self._inv_norms)))
        return [NamedSharding(self.mesh, P(self.shard_axis)
                              if split and i < n_cand and v.shape[0] == n_rows
                              else P())
                for i, v in enumerate(arrays)]

    def _serve_args(self):
        """``_serve_leaves``' arrays, placed on the mesh once for a mesh
        engine."""
        arrays, _ = self._serve_leaves()
        if self.mesh is None:
            return arrays
        if self._placed is None:
            self._placed = [jax.device_put(v, sh) for v, sh in
                            zip(arrays, self._serve_shardings(arrays))]
        return self._placed

    def _serve_fn(self, n: int):
        """The whole-request jit for top-``n``: ``fn(arrays, xb)`` over
        ``_serve_args()``, encode → score → select; cached per n."""
        fn = self._serve_cache.get(n)
        if fn is None:
            _, rebuild = self._serve_leaves()

            def _serve(arrays, xb):
                # runs only while JAX traces: once per panel shape
                self.counters["serve_traces"] += 1
                with TraceAnnotation("serve.engine.trace",
                                     rows=xb.shape[0], n=n):
                    eng = copy.copy(self)
                    eng.index, eng._inv_norms, eng.params = rebuild(arrays)
                    return eng.retrieve_codes(eng.encode_queries(xb), n)

            fn = self._serve_cache[n] = jax.jit(_serve)
        return fn

    def encode_queries(self, x: jax.Array) -> SparseCodes:
        """Dense (Q?, d) embeddings -> fixed-k query codes.  Kernel path:
        ``fused_encode`` (abs-top-k epilogue in VMEM, no (Q, h)
        pre-activations in HBM); jnp path: ``sae.encode``."""
        if self.params is None:
            raise EngineConfigError("encoding queries requires SAE params")
        if not self.use_fused:
            return sae.encode(self.params, x, self.k)

        def enc(x, w, b):
            return fused_encode(x, w, b, self.k)[:2]

        if self.mesh is not None:
            # XLA partitions no Mosaic kernel: each device encodes the
            # whole (small) query batch, which the shards all need
            enc = compat.shard_map(enc, mesh=self.mesh, in_specs=P(),
                                   out_specs=P(), check=False)
        w_enc = self.params["w_enc"]
        return SparseCodes(*enc(x, w_enc, self.params["b_enc"]),
                           dim=w_enc.shape[1])

    def prep_query(self, q: SparseCodes) -> PreppedQuery:
        return prep_query(self.index, q, self.mode, self.params)

    def retrieve_codes(
        self, q: SparseCodes, n: int
    ) -> tuple[jax.Array, jax.Array]:
        """Serve a request whose queries are already compressed codes."""
        if self.segments is not None:
            n = validate_topn(n, self.segments.n_rows)
            validate_query_codes(q, h=self.index.codes.dim)
            return self.segments.retrieve(
                q, n, use_fused=self.use_fused, precision=self.precision
            )
        n = validate_topn(n, self.index.codes.n)
        validate_query_codes(q, h=self.index.codes.dim)
        if self.stage == "two_stage":
            return two_stage_retrieve(
                self.index, self.inverted, q, n,
                use_fused=self.use_fused, precision=self.precision,
                candidate_fraction=self.candidate_fraction,
                cache=self._two_stage_cache, stage1=self.stage1,
            )
        pq = self.prep_query(q)
        if self.mesh is not None:
            from repro.distributed.retrieve import distributed_retrieve_prepped

            return distributed_retrieve_prepped(
                self.index, pq, n,
                mesh=self.mesh, axis_name=self.shard_axis,
                use_fused=self.use_fused, inv_norms=self._inv_norms,
                precision=self.precision,
            )
        return retrieve_prepped(
            self.index, pq, n,
            use_fused=self.use_fused, inv_norms=self._inv_norms,
            precision=self.precision,
        )

    def retrieve_dense(self, x: jax.Array, n: int) -> RetrievalResponse:
        """The end-to-end request: dense embeddings in, a
        ``RetrievalResponse`` out — one jit per distinct ``n``.

        ``resp.scores``/``resp.ids`` (equivalently ``resp[:2]``) are
        exactly the panels the tuple-era API returned.  The stamped
        ``ServingStatus`` is the healthy configured path (step 0, not
        degraded) — the guard layer replaces it with what actually
        happened when serving degrades.  The call returns once the work
        is dispatched; device completion stays the caller's
        ``block_until_ready``.  It is the ``serve.engine.dispatch`` span.
        """
        with TraceAnnotation("serve.engine.dispatch") as span:
            d = None if self.params is None else self.params["w_enc"].shape[0]
            validate_dense_query(x, d=d)
            validate_topn(
                n,
                self.index.codes.n if self.segments is None
                else self.segments.n_rows,
            )
            squeeze = x.ndim == 1
            xb = x[None] if squeeze else x
            span.set_metadata(rows=xb.shape[0], n=n)
            # Shape-stable serve path: every panel the jit sees is padded to
            # a BLOCK_Q multiple with zero rows (scored and sliced off), so
            # a lone request and a coalesced microbatch panel of the same
            # bucket compile and compute IDENTICALLY — the bit-identity the
            # batcher promises is structural, not an XLA accident — and
            # per-request traffic of varied widths retraces once per bucket,
            # not once per width.
            rows = int(xb.shape[0])
            pad = (-rows) % BLOCK_Q
            if pad:
                xb = jnp.concatenate(
                    [xb, jnp.zeros((pad, xb.shape[1]), dtype=xb.dtype)],
                    axis=0,
                )
            if self.segments is not None or self.stage == "two_stage":
                # segment content mutates between requests, and two-stage
                # runs host work between its two jits — neither request can
                # be one monolithic jit (segments: arrays would bake in as
                # constants; the per-segment retrieves are module-level jits
                # keyed on segment array SHAPES, so shape-preserving
                # mutations recompile nothing and ``apply_update`` never
                # invalidates).  The encode is its own cached jit.
                fn = self._serve_cache.get("encode")
                if fn is None:
                    fn = jax.jit(lambda xb: self.encode_queries(xb))
                    self._serve_cache["encode"] = fn
                codes = fn(xb)
                scores, ids = self.retrieve_codes(codes, n)
            else:
                scores, ids = self._serve_fn(n)(self._serve_args(), xb)
            if pad:
                scores, ids = scores[:rows], ids[:rows]
            if squeeze:
                scores, ids = scores[0], ids[0]
            return RetrievalResponse(
                scores=scores, ids=ids,
                status=ServingStatus(path=path_name(self)),
                queue_us=0.0,
            )
