"""The serving stack: ``MicrobatchServer`` → ``GuardedEngine`` →
``RetrievalEngine`` → kernels.

Each layer records its steps as ``jax.profiler.TraceAnnotation`` spans,
host events on the profiler's clock beside the device's ops.  With no
trace recording a span costs about a microsecond.

=========================  ==========  ================================
span                       thread      covers (stats)
=========================  ==========  ================================
``serve.batcher.submit``   caller      validation and enqueue of one
                                       request (``request``: its number)
``serve.batcher.coalesce`` dispatcher  waiting for arrivals until the
                                       panel is drained (``rows``)
``serve.batcher.panel``    dispatcher  one whole panel (``panel``,
                                       ``rows``, ``bucket``, ``requests``:
                                       the request numbers it carries)
``serve.batcher.assemble`` dispatcher  concatenation and zero padding
``serve.guard.admit``      dispatcher  validation and the host-side
                                       finiteness check (``rows``)
``serve.engine.dispatch``  dispatcher  validation, padding and the serve
                                       jit's call, not its device work
                                       (``rows``, ``n``)
``serve.engine.trace``     dispatcher  one trace of the serve program
                                       (``rows``, ``n``)
``serve.batcher.wait``     dispatcher  blocking on the panel's result
``serve.batcher.resolve``  dispatcher  per-request slicing and
                                       ``set_result`` with its callbacks
                                       (``requests``: how many)
=========================  ==========  ================================

``RetrievalEngine.counters["serve_traces"]`` counts the traces of the
serve program, one per panel shape and top-n.
"""
from repro.serving.batcher import DEFAULT_BUCKETS, MicrobatchServer
from repro.serving.config import (
    PRECISIONS,
    EngineConfig,
    check_precision,
)
from repro.serving.engine import (
    PreppedQuery,
    RetrievalEngine,
    mode_inv_norms,
    path_name,
    prep_query,
    resolve_stage1,
    retrieve_prepped,
    select_retrieve_fn,
    validate_dense_query,
    validate_query_codes,
    validate_topn,
)
from repro.serving.faults import (
    FAULTS,
    FaultInjector,
    corrupt_postings,
    flip_delta_byte,
    flip_index_byte,
    poison_queries,
)
from repro.serving.guard import (
    Deadline,
    GuardedEngine,
    SelfCheckReport,
    self_check,
)
from repro.serving.response import RetrievalResponse, ServingStatus

__all__ = [
    "RetrievalEngine",
    "EngineConfig",
    "RetrievalResponse",
    "MicrobatchServer",
    "DEFAULT_BUCKETS",
    "PreppedQuery",
    "prep_query",
    "retrieve_prepped",
    "select_retrieve_fn",
    "mode_inv_norms",
    "path_name",
    "resolve_stage1",
    "check_precision",
    "PRECISIONS",
    "validate_dense_query",
    "validate_query_codes",
    "validate_topn",
    "FAULTS",
    "FaultInjector",
    "corrupt_postings",
    "flip_delta_byte",
    "flip_index_byte",
    "poison_queries",
    "Deadline",
    "GuardedEngine",
    "SelfCheckReport",
    "ServingStatus",
    "self_check",
]
