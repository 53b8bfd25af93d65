"""Request guards + degradation ladder: hardened serving (ISSUE 6 tentpole).

``GuardedEngine`` wraps a ``RetrievalEngine`` with everything the bare
engine deliberately does not do:

* **admission** — shape/dtype/top-n validation raising typed
  ``InvalidQueryError``s that name the offending argument, plus a
  host-side finiteness check on the dense query bytes (reject, or
  sanitize-to-zero with the count reported) so NaN/Inf never reaches a
  kernel;
* **a per-request deadline budget** — ``Deadline`` tracks a monotonic
  budget; slow paths (shard retry backoff, injected stalls) are abandoned
  when it runs out.  The deadline never abandons the *final* answer: the
  remaining ladder rungs still serve, and the response is tagged
  ``deadline_exceeded`` instead of timing out empty-handed;
* **the degradation ladder** — on a fault, serving steps down
  ``two-stage-device → two-stage-host → sharded → unsharded → int8 →
  exact-quantized → fp32 ref → full-score floor`` (whichever rungs the
  engine's configuration actually has),
  re-serving the SAME request on the next-safest path.  Every response
  carries a ``ServingStatus`` naming the path taken, whether it is
  degraded, and why — a fault is an annotated answer, never a crash and
  never a silently wrong result;
* **startup self-check** — ``self_check`` verifies the index checksum
  (``core.retrieval.verify_index``: a single flipped byte is a typed
  ``IndexIntegrityError``), the inverted-index checksum when the engine
  serves two-stage (``core.inverted_index.verify_inverted_index`` — so
  ``corrupt-postings`` is a startup failure, not a first-request
  surprise), and runs a deterministic canary batch through
  the configured path, asserting it against the reference contract
  (int8: kernel↔ref bit-equality; exact: f32-rounding agreement) before
  the engine accepts traffic;
* **distributed hardening** — a dead shard gets bounded retry with
  exponential backoff; if it stays dead, the request is served by a
  partial merge over the surviving shards
  (``distributed.retrieve.partial_retrieve_prepped``) with the achieved
  coverage (the recall bound) reported in the status.

Fault injection (``serving.faults.FaultInjector``) plugs into the same
decision points deterministically, which is how the fault-matrix suite
exercises every rung without a real outage.
"""
from __future__ import annotations

import math
import time
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import sae
from repro.core.quantized_codes import QuantizedCodes
from repro.core.inverted_index import verify_inverted_index
from repro.core.retrieval import (
    dequantize_index,
    index_codes_f32,
    score_reconstructed,
    score_sparse,
    top_n,
    verify_index,
)
from repro.core.types import SparseCodes
from repro.errors import (
    DeadlineExceededError,
    DegradationExhaustedError,
    IndexIntegrityError,
    InvalidQueryError,
    RetrievalError,
    SelfCheckError,
    ShardFailureError,
)
from repro.serving.config import EngineConfig
from repro.serving.engine import (
    RetrievalEngine,
    path_name,
    resolve_stage1,
    validate_dense_query,
    validate_topn,
)
from repro.serving.response import (  # noqa: F401 — re-exported API
    RetrievalResponse,
    ServingStatus,
)


class Deadline:
    """A per-request wall-clock budget on the host's monotonic clock.

    ``budget_ms=None`` never expires (the default: guards should not
    impose latency policy unless asked).  ``check(stage)`` raises a
    typed ``DeadlineExceededError`` naming the stage that overran.
    """

    def __init__(self, budget_ms: Optional[float] = None):
        self.budget_ms = budget_ms
        self._t0 = time.monotonic()

    @property
    def elapsed_ms(self) -> float:
        return (time.monotonic() - self._t0) * 1e3

    @property
    def remaining_ms(self) -> float:
        if self.budget_ms is None:
            return math.inf
        return self.budget_ms - self.elapsed_ms

    @property
    def expired(self) -> bool:
        return self.remaining_ms <= 0.0

    def check(self, stage: str) -> None:
        if self.expired:
            raise DeadlineExceededError(
                f"deadline budget {self.budget_ms}ms exhausted at "
                f"{stage} ({self.elapsed_ms:.1f}ms elapsed)"
            )


class SelfCheckReport(NamedTuple):
    """What the startup self-check verified before accepting traffic."""

    index_verified: bool      # content checksum matched
    canary_q: int             # canary batch size served
    canary_n: int             # top-n of the canary request
    path: str                 # primary path description
    kernel_vs_ref: Optional[str]  # "bit-identical" | "allclose" | None
                              # (None: primary already IS the ref path)
    max_abs_diff: float       # worst canary score delta vs reference


def _canary_queries(engine: RetrievalEngine, canary_q: int):
    """A deterministic canary batch (no RNG — self-checks must be
    reproducible): the first decoder atoms as dense embeddings when the
    engine can encode, else the index's own first rows as query codes."""
    if engine.params is not None:
        q = min(canary_q, engine.params["w_dec"].shape[0])
        return engine.params["w_dec"][:q, :], None
    codes = index_codes_f32(engine.index)
    q = min(canary_q, codes.values.shape[0])
    return None, SparseCodes(
        values=codes.values[:q], indices=codes.indices[:q], dim=codes.dim
    )


def self_check(
    engine: RetrievalEngine,
    *,
    canary_q: int = 4,
    canary_n: int = 8,
    require_checksum: bool = True,
) -> SelfCheckReport:
    """Verify index integrity, then serve a canary batch and hold it to
    the configured path's reference contract.

    Index bytes are checked against the build-time checksum first
    (``IndexIntegrityError`` on mismatch — a single flipped byte fails
    here, before any kernel runs).  The canary then asserts:

    * sanity on the primary path's own output — finite scores, ids in
      range, scores sorted descending (catches poisoned norms that a
      checksumless index could smuggle in);
    * when the primary path is a fused kernel, agreement with the jnp
      reference twin: **bit-equality** for int8 precision (generation
      5's kernel↔ref contract) and f32-rounding agreement (allclose +
      id-set overlap) for the exact generations.

    Raises ``SelfCheckError`` / ``IndexIntegrityError``; returns a
    ``SelfCheckReport`` when the engine is fit to accept traffic.
    """
    segments = getattr(engine, "segments", None)
    if segments is not None:
        # segmented engines verify EVERY segment's content CRC32 — a
        # flipped byte in the small delta is caught with the same
        # startup rigor as one in the base
        segments.verify(require=require_checksum)
    else:
        verify_index(engine.index, require=require_checksum)
    if engine.inverted is not None:
        # two-stage engines also serve from posting lists: hold them to
        # the same build-time checksum contract so corrupt-postings is a
        # startup failure, not a first-request surprise
        verify_inverted_index(engine.inverted, require=require_checksum)
    canary_n = min(canary_n, engine.index.codes.n)
    if segments is not None:
        # dead rows never surface; an underfull canary would trip the
        # finiteness check on its (-inf, -1) padding
        canary_n = max(1, min(canary_n, segments.n_alive))

    xq, qcodes = _canary_queries(engine, canary_q)

    def serve(e):
        # the canary runs each engine directly, never through the ladder:
        # a path that cannot serve fails startup here instead of being
        # degraded around on every request that follows
        try:
            if xq is not None:
                return e.retrieve_dense(xq, canary_n).pair
            return e.retrieve_codes(qcodes, canary_n)
        except RetrievalError:
            raise
        except Exception as err:  # noqa: BLE001 — surfaced typed, chained
            raise SelfCheckError(
                f"{path_name(e)} cannot serve the canary: "
                f"{type(err).__name__}: {err}"
            ) from err

    scores, ids = serve(engine)
    s = np.asarray(scores)
    i = np.asarray(ids)
    n_cand = engine.index.codes.n
    if not np.all(np.isfinite(s)):
        raise SelfCheckError(
            "canary produced non-finite scores — index norms or params "
            "are poisoned"
        )
    if segments is not None:
        # segmented retrieval returns ITEM ids — the valid set is the
        # alive ids, not a contiguous [0, N) range
        valid = set(int(v) for v in segments.alive_ids())
        bad = [int(v) for v in i.ravel() if int(v) not in valid]
        if bad:
            raise SelfCheckError(
                f"canary returned ids outside the alive item set "
                f"(first: {bad[0]})"
            )
    elif np.any(i < 0) or np.any(i >= n_cand):
        raise SelfCheckError(
            f"canary returned candidate ids outside [0, {n_cand})"
        )
    if np.any(np.diff(s, axis=-1) > 1e-6):
        raise SelfCheckError("canary scores are not sorted descending")

    kernel_vs_ref = None
    max_diff = 0.0
    # two-stage engines skip the kernel-vs-ref comparison: the path is
    # structurally approximate (candidate generation, not scoring, is
    # what differs from the reference), so bit/allclose contracts don't
    # apply — its quality bound is recall-gated in benchmarks instead.
    # The sanity checks above (finite, in-range, sorted) still ran.
    if (engine.use_fused or engine.mesh is not None) \
            and engine.stage == "single":
        ref = RetrievalEngine(
            segments if segments is not None else engine.index,
            engine.params,
            config=EngineConfig(mode=engine.mode, use_kernel=False,
                                precision=engine.precision),
        )
        rs, ri = serve(ref)
        rs, ri = np.asarray(rs), np.asarray(ri)
        max_diff = float(np.max(np.abs(s - rs)))
        if engine.precision == "int8":
            # generation 5 contract: kernel and ref are BIT-identical
            if not (np.array_equal(s, rs) and np.array_equal(i, ri)):
                raise SelfCheckError(
                    "int8 canary: kernel and reference disagree — the "
                    "gen-5 contract is bit-equality (max |Δscore| "
                    f"{max_diff:.3e})"
                )
            kernel_vs_ref = "bit-identical"
        else:
            overlap = np.mean([
                len(set(a) & set(b)) / len(a) for a, b in zip(i, ri)
            ])
            if not np.allclose(s, rs, rtol=1e-5, atol=1e-5) or overlap < 0.9:
                raise SelfCheckError(
                    "exact canary: kernel and reference disagree beyond "
                    f"f32 rounding (max |Δscore| {max_diff:.3e}, id "
                    f"overlap {overlap:.2f})"
                )
            kernel_vs_ref = "allclose"

    return SelfCheckReport(
        index_verified=engine.index.checksum is not None,
        canary_q=int(s.shape[0]), canary_n=canary_n,
        path=path_name(engine), kernel_vs_ref=kernel_vs_ref,
        max_abs_diff=max_diff,
    )


def _stage1_impl(cfg) -> Optional[str]:
    """The resolved stage-1 implementation of a ladder config (None for
    single-stage rungs) — part of the rung identity, so a device and a
    host two-stage rung never dedup into one."""
    if cfg.get("stage") != "two_stage":
        return None
    return resolve_stage1(cfg.get("stage1", "auto"))


class GuardedEngine:
    """A ``RetrievalEngine`` behind admission control, a deadline budget,
    and the degradation ladder.  See the module docstring for semantics.

    engine:       the configured primary serving engine.
    deadline_ms:  default per-request budget (None = unbounded);
                  per-call override via ``retrieve_dense(...,
                  deadline_ms=...)``.
    on_invalid:   "reject" (typed error on non-finite queries — the
                  default; bad bytes are the caller's bug) or "sanitize"
                  (zero them, serve, and report the count as degraded).
    retries:      shard retry attempts before the partial-merge fallback.
    backoff_s:    base of the exponential retry backoff.
    injector:     a ``serving.faults.FaultInjector`` (None in
                  production) consulted at each decision point.
    fallback_index: served from (precision forced to its best exact
                  setting) if the PRIMARY index fails its integrity
                  check at startup — the "stale-but-verified replica"
                  pattern; requests are then degraded from the start.
    run_self_check: run ``self_check`` at construction and refuse to
                  build a guard over an engine that fails it.
    """

    def __init__(
        self,
        engine: RetrievalEngine,
        *,
        deadline_ms: Optional[float] = None,
        on_invalid: str = "reject",
        retries: int = 2,
        backoff_s: float = 0.01,
        injector=None,
        fallback_index=None,
        run_self_check: bool = False,
        canary_q: int = 4,
        canary_n: int = 8,
    ):
        if on_invalid not in ("reject", "sanitize"):
            raise ValueError(
                f"on_invalid must be 'reject' or 'sanitize', got "
                f"{on_invalid!r}"
            )
        self.deadline_ms = deadline_ms
        self.on_invalid = on_invalid
        self.retries = int(retries)
        self.backoff_s = float(backoff_s)
        self.injector = injector
        self.degraded_from_start: Optional[str] = None
        self.counters = {
            "requests": 0, "degraded": 0, "rejected": 0, "sanitized": 0,
        }
        self.self_check_report: Optional[SelfCheckReport] = None
        # fraction of the alive catalog a segmented engine still serves
        # (< 1.0 only after a corrupt-delta shed to base-only)
        self._segment_coverage: float = 1.0

        if run_self_check:
            try:
                self.self_check_report = self_check(
                    engine, canary_q=canary_q, canary_n=canary_n
                )
            except IndexIntegrityError as err:
                seg = getattr(engine, "segments", None)
                shed = None
                if seg is not None and seg.delta is not None:
                    # a segmented engine carries its own stale-but-verified
                    # replica: the immutable base.  If the base's CRC still
                    # holds, drop the corrupt delta and serve base-only —
                    # partial coverage, never corrupt bytes.
                    try:
                        verify_index(seg.base)
                    except IndexIntegrityError:
                        pass  # base is poisoned too — fall to the replica
                    else:
                        shed = seg.base_only()
                if shed is not None:
                    engine = RetrievalEngine(
                        shed, engine.params,
                        config=EngineConfig(
                            mode=engine.mode,
                            use_kernel=engine.use_kernel,
                            precision=engine.precision,
                        ),
                    )
                    self.self_check_report = self_check(
                        engine, canary_q=canary_q, canary_n=canary_n
                    )
                    self._segment_coverage = float(seg.base_coverage)
                    self.degraded_from_start = (
                        f"delta segment failed integrity check ({err}); "
                        "serving base-only at coverage "
                        f"{self._segment_coverage:.3f}"
                    )
                    self.engine = engine
                    self._ladder = self._build_ladder()
                    self._rung_engines = {0: engine}
                    return
                if fallback_index is None:
                    raise
                verify_index(fallback_index)
                engine = RetrievalEngine(
                    fallback_index, engine.params,
                    config=EngineConfig(
                        mode=engine.mode, use_kernel=engine.use_kernel,
                        mesh=engine.mesh, shard_axis=engine.shard_axis,
                        precision=(engine.precision if isinstance(
                            fallback_index.codes, QuantizedCodes)
                            else "exact"),
                    ),
                )
                self.self_check_report = self_check(
                    engine, canary_q=canary_q, canary_n=canary_n
                )
                self.degraded_from_start = (
                    f"primary index failed integrity check ({err}); "
                    "serving from verified fallback index"
                )
        self.engine = engine
        self._ladder = self._build_ladder()
        self._rung_engines: dict[int, Optional[RetrievalEngine]] = {
            0: engine
        }

    # ------------------------------------------------------------- ladder
    def _build_ladder(self):
        """(name, config) per rung, primary first, strictly safer as the
        step index grows; the kernel-free full-score floor is always
        last.  Configs that coincide with an earlier rung are dropped,
        so the ladder only contains genuinely distinct paths."""
        e = self.engine
        quantized = isinstance(e.index.codes, QuantizedCodes)
        segmented = getattr(e, "segments", None) is not None
        cfgs = []
        if e.stage == "two_stage":
            # two-stage occupies the TOP rungs: fastest, but approximate
            # and dependent on posting-list integrity.  A device stage-1
            # failure (jit/runtime fault) sheds to the host stage-1
            # oracle first — bit-identical candidates, no device union —
            # and only then to the exact single-stage scan of the same
            # precision/backend (actual postings corruption fails BOTH
            # stage-1 implementations, since they share the one inverted
            # index, and lands there)
            cfgs.append(dict(mesh=None, precision=e.precision,
                             use_fused=e.use_fused, dequant=False,
                             stage="two_stage", stage1=e.stage1))
            cfgs.append(dict(mesh=None, precision=e.precision,
                             use_fused=e.use_fused, dequant=False,
                             stage="two_stage", stage1="host"))
        cfgs += [
            dict(mesh=e.mesh, precision=e.precision,
                 use_fused=e.use_fused, dequant=False, stage="single"),
            # shed the mesh first: a healthy single device beats retrying
            # a broken collective
            dict(mesh=None, precision=e.precision,
                 use_fused=e.use_fused, dequant=False, stage="single"),
        ]
        if e.precision == "int8":
            cfgs.append(dict(mesh=None, precision="exact",
                             use_fused=e.use_fused, dequant=False,
                             stage="single"))
        # the pre-floor rung: fp32 index, jnp reference path.  Segmented
        # engines keep the base's stored format here — dequantizing the
        # base alone would break the quantized-delta parity contract, so
        # their fp32 answer comes from the full-score floor instead.
        cfgs.append(dict(mesh=None, precision="exact",
                         use_fused=False, dequant=quantized and not segmented,
                         stage="single"))
        ladder, seen = [], set()
        for cfg in cfgs:
            key = (cfg["mesh"] is None, cfg["precision"],
                   cfg["use_fused"], cfg["dequant"], cfg["stage"],
                   _stage1_impl(cfg))
            if key in seen:
                continue
            seen.add(key)
            ladder.append((self._cfg_name(cfg), cfg))
        ladder.append(("fp32-fullscore", None))
        return ladder

    def _cfg_name(self, cfg) -> str:
        quantized = (isinstance(self.engine.index.codes, QuantizedCodes)
                     and not cfg["dequant"])
        fmt = ("int8" if cfg["precision"] == "int8"
               else "quantized" if quantized else "fp32")
        backend = "kernel" if cfg["use_fused"] else "ref"
        sharded = "-sharded" if cfg["mesh"] is not None else ""
        impl = _stage1_impl(cfg)
        prefix = f"two-stage-{impl}-" if impl is not None else ""
        return f"{prefix}{fmt}-{backend}{sharded}"

    @property
    def ladder(self) -> tuple[str, ...]:
        """The rung names, primary first (for logs/docs/tests)."""
        return tuple(name for name, _ in self._ladder)

    def _engine_for(self, step: int) -> Optional[RetrievalEngine]:
        """Lazily build (and memoize) the rung's engine; None = the
        kernel-free full-score floor."""
        if step in self._rung_engines:
            return self._rung_engines[step]
        _, cfg = self._ladder[step]
        if cfg is None:
            eng = None
        else:
            e = self.engine
            seg = getattr(e, "segments", None)
            if seg is not None:
                # rungs below a segmented primary serve the SAME segments
                # (base + delta + deletion masks) at the rung's
                # precision/backend — shedding a kernel generation must
                # not silently resurrect deleted rows or drop the delta
                index = seg
            else:
                index = (dequantize_index(e.index) if cfg["dequant"]
                         else e.index)
            two = cfg.get("stage") == "two_stage"
            rung_cfg = EngineConfig(
                mode=e.mode, use_kernel=cfg["use_fused"], mesh=cfg["mesh"],
                shard_axis=e.shard_axis, precision=cfg["precision"],
                stage=cfg.get("stage", "single"),
                **(dict(candidate_fraction=e.candidate_fraction,
                        inverted_cap=e.inverted_cap,
                        stage1=cfg.get("stage1", "auto")) if two else {}),
            )
            eng = RetrievalEngine(index, e.params, config=rung_cfg)
            if two and e.inverted is not None:
                # every two-stage rung serves from the SAME inverted
                # index as the primary engine (not a private rebuild):
                # the device→host shed covers device-side faults only,
                # and genuine postings corruption must fail both rungs
                eng.inverted = e.inverted
        self._rung_engines[step] = eng
        return eng

    # -------------------------------------------------------------- floor
    def _fullscore(self, x, n: int):
        """The ladder's floor: full-score + top-n with every kernel and
        fusion OFF — the most battle-tested composition in the repo (it
        is the oracle every other path is tested against)."""
        e = self.engine
        codes = sae.encode(e.params, x, e.k)
        seg = getattr(e, "segments", None)
        if seg is not None:
            # the segmented floor full-scores the COMPACTED survivors
            # (base + delta, dead rows dropped) so deleted ids cannot
            # surface even here, then translates positions to item ids
            comp = seg.compact()
            index = comp.base
            if isinstance(index.codes, QuantizedCodes):
                index = dequantize_index(index)
            scores = score_sparse(index, codes, use_kernel=False)
            n_eff = min(n, index.codes.n)
            vals, pos = top_n(scores, n_eff)
            ids = jnp.asarray(np.asarray(comp.base_ids))[pos]
            if n_eff < n:
                pad = [(0, 0)] * (vals.ndim - 1) + [(0, n - n_eff)]
                vals = jnp.pad(vals, pad, constant_values=-jnp.inf)
                ids = jnp.pad(ids, pad, constant_values=-1)
            return vals, ids
        index = (dequantize_index(e.index)
                 if isinstance(e.index.codes, QuantizedCodes) else e.index)
        if e.mode == "reconstructed":
            scores = score_reconstructed(index, codes, e.params,
                                         use_kernel=False)
        else:
            scores = score_sparse(index, codes, use_kernel=False)
        return top_n(scores, n)

    # ---------------------------------------------------------- admission
    def _admit_values(self, x):
        """Host-side finiteness check on the query bytes — the one check
        that cannot be trace-safe.  Reject names the first bad position;
        sanitize zeroes the bad entries and reports how many."""
        arr = np.asarray(x)
        bad = ~np.isfinite(arr)
        nbad = int(bad.sum())
        if nbad == 0:
            return x, 0
        pos = tuple(int(v) for v in np.argwhere(bad)[0])
        if self.on_invalid == "reject":
            raise InvalidQueryError(
                f"x: {nbad} non-finite value(s) in the query batch, "
                f"first at position {pos} ({arr[pos]!r}); rejected at "
                "admission — non-finite embeddings never reach the kernel"
            )
        arr = np.where(bad, 0.0, arr).astype(arr.dtype, copy=False)
        return jnp.asarray(arr), nbad

    # ----------------------------------------------------------- sharding
    def _serve_sharded(self, eng: RetrievalEngine, x, n: int,
                       deadline: Deadline):
        """Bounded retry with exponential backoff, then partial merge.

        Returns ``(scores, ids, retries, coverage, fault_reason)``.  The
        deadline is charged for injected stalls and checked before each
        backoff sleep — an expired budget skips straight to the partial
        merge (serve *something*) rather than burning more wall-clock.
        """
        from repro.distributed.retrieve import (
            mesh_shard_count, partial_retrieve_prepped,
        )

        inj = self.injector
        n_shards = mesh_shard_count(eng.mesh, eng.shard_axis)
        dead: frozenset[int] = frozenset()
        attempt = 0
        for attempt in range(self.retries + 1):
            if inj is not None:
                inj.stall(attempt)        # slow shard: host-visible stall
            dead = inj.dead_shards(attempt) if inj is not None else frozenset()
            if not dead:
                scores, ids, *_ = eng.retrieve_dense(x, n)
                fault = (f"shard recovered after {attempt} retr"
                         f"{'y' if attempt == 1 else 'ies'}"
                         if attempt else None)
                return scores, ids, attempt, 1.0, fault
            if attempt < self.retries:
                deadline.check(f"shard retry backoff (attempt {attempt})")
                time.sleep(self.backoff_s * (2 ** attempt))

        # retries exhausted: merge what survived
        codes = eng.encode_queries(x)
        pq = eng.prep_query(codes)
        scores, ids, coverage = partial_retrieve_prepped(
            eng.index, pq, n,
            n_shards=n_shards, dead_shards=dead, use_fused=eng.use_fused,
            precision=eng.precision,
        )
        fault = (
            f"shard(s) {sorted(dead)} dead after {self.retries} retries; "
            f"partial merge over {n_shards - len(dead)}/{n_shards} shards"
        )
        return scores, ids, attempt, coverage, fault

    # ------------------------------------------------------------ serving
    def retrieve_dense(self, x, n: int, *,
                       deadline_ms: Optional[float] = None
                       ) -> RetrievalResponse:
        """Serve one guarded request: a ``RetrievalResponse`` whose
        ``ServingStatus`` names the ladder rung that actually answered.

        Admission failures raise typed errors (the caller sent garbage);
        every fault PAST admission is absorbed by the ladder — the
        request is re-served on the next rung down and the status says
        so.  Only when every rung fails does ``DegradationExhaustedError``
        surface, chaining each rung's reason.
        """
        deadline = Deadline(self.deadline_ms if deadline_ms is None
                            else deadline_ms)
        self.counters["requests"] += 1
        try:
            with TraceAnnotation("serve.guard.admit") as span:
                seg = getattr(self.engine, "segments", None)
                n = validate_topn(n, self.engine.index.codes.n
                                  if seg is None else seg.n_rows)
                d = (None if self.engine.params is None
                     else self.engine.params["w_enc"].shape[0])
                validate_dense_query(x, d=d)
                span.set_metadata(rows=1 if x.ndim == 1 else x.shape[0])
                x, sanitized = self._admit_values(x)
        except InvalidQueryError:
            self.counters["rejected"] += 1
            raise
        if sanitized:
            self.counters["sanitized"] += 1

        mesh = self.engine.mesh
        shards_total = 1
        if mesh is not None:
            from repro.distributed.retrieve import mesh_shard_count

            shards_total = mesh_shard_count(mesh, self.engine.shard_axis)

        faults: list[str] = []
        for step, (name, _) in enumerate(self._ladder):
            try:
                if self.injector is not None:
                    self.injector.before_step(step)
                eng = self._engine_for(step)
                retries, coverage, fault = 0, 1.0, None
                shards_used = shards_total if step == 0 else 1
                if eng is None:
                    scores, ids = self._fullscore(x, n)
                elif eng.mesh is not None:
                    scores, ids, retries, coverage, fault = (
                        self._serve_sharded(eng, x, n, deadline))
                    dead_now = round(shards_total * (1.0 - coverage))
                    shards_used = shards_total - dead_now
                else:
                    scores, ids, *_ = eng.retrieve_dense(x, n)
            except RetrievalError as err:
                faults.append(f"{name}: {err}")
                continue
            except Exception as err:  # noqa: BLE001 — the ladder exists
                # exactly so an unanticipated kernel/runtime fault on one
                # rung degrades instead of crashing the request
                faults.append(f"{name}: {type(err).__name__}: {err}")
                continue

            # a base-only shed caps coverage at the surviving fraction
            coverage = min(float(coverage), self._segment_coverage)
            reasons = faults + ([fault] if fault else [])
            if self.degraded_from_start:
                reasons.insert(0, self.degraded_from_start)
            if sanitized:
                reasons.insert(
                    0, f"sanitized {sanitized} non-finite query value(s)"
                )
            degraded = bool(
                step > 0 or sanitized or coverage < 1.0
                or self.degraded_from_start
            )
            if degraded:
                self.counters["degraded"] += 1
            status = ServingStatus(
                path=name, step=step, degraded=degraded,
                fault="; ".join(reasons) if reasons else None,
                shards_total=shards_total, shards_used=shards_used,
                coverage=float(coverage), retries=retries,
                sanitized=sanitized,
                deadline_exceeded=deadline.expired,
            )
            return RetrievalResponse(
                scores=scores, ids=ids, status=status, queue_us=0.0,
            )

        raise DegradationExhaustedError(
            "every degradation-ladder rung failed for this request: "
            + " | ".join(faults)
        )

    def self_check(self, **kw) -> SelfCheckReport:
        """Run (or re-run) the startup self-check on the current engine."""
        self.self_check_report = self_check(self.engine, **kw)
        return self.self_check_report
