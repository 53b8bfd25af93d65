"""The system under test, built from what the benchmark generated.

The program makes the catalog codes (``fused_encode`` over the seeded
catalog, chunk by chunk), the index (``build_index`` in the
configuration's format) and the serving stack
``MicrobatchServer -> GuardedEngine(run_self_check=True) ->
RetrievalEngine``.  Between the batcher and the guard sits ``PanelSpan``, a
thin proxy that records a ``bench.panel`` span around each panel the
batcher hands on, blocking on the result inside it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


class PanelSpan:
    """What the batcher calls in place of the guard: the guard's
    ``retrieve_dense`` inside a ``bench.panel`` trace span."""

    def __init__(self, guard):
        self.guard = guard
        self.engine = guard.engine
        self.panels: list[tuple[float, float, int]] = []   # (t0, t1, rows)

    def retrieve_dense(self, x, n):
        rows = int(x.shape[0])
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.panel", rows=rows):
            resp = self.guard.retrieve_dense(x, n)
            jax.block_until_ready(resp.ids)
        self.panels.append((t0, time.monotonic(), rows))
        return resp


def build_engine(cfg: dict, catalog, params: dict, *, use_kernel="auto"):
    """Codes, index and guarded engine for one configuration.  Raises when
    the engine would not serve on the configuration's path."""
    from repro.core import build_index
    from repro.core.types import SparseCodes
    from repro.kernels.fused_encode import fused_encode
    from repro.serving import (
        EngineConfig, GuardedEngine, RetrievalEngine, path_name,
    )

    k, h = int(cfg["k"]), int(cfg["h"])
    enc = jax.jit(lambda x, w, b: tuple(fused_encode(x, w, b, k)[:2]))
    vals, idx = [], []
    for c in range(catalog.n_chunks):
        v, i = enc(catalog.chunk_rows(c), params["w_enc"], params["b_enc"])
        vals.append(v)
        idx.append(i)
    codes = SparseCodes(values=jnp.concatenate(vals),
                        indices=jnp.concatenate(idx), dim=h)
    del vals, idx
    index = build_index(codes, quantize=cfg["index_format"] == "q8")
    del codes
    engine = RetrievalEngine(index, params,
                             config=EngineConfig(use_kernel=use_kernel))
    if path_name(engine) != cfg["path"]:
        raise RuntimeError(f"engine serves on {path_name(engine)}, the "
                           f"configuration states {cfg['path']}")
    return GuardedEngine(engine, run_self_check=True)


def query_pool(queries: np.ndarray) -> list:
    """One (1, d) device array per request, made before the window."""
    dev = jax.device_put(np.asarray(queries, np.float32))
    rows = jnp.split(dev, dev.shape[0])
    jax.block_until_ready(rows)
    return rows


def warm_up(server, pool: list, top_n: int, panels: list[int],
            timeout: float) -> None:
    """Send each warm-up panel as one burst of single-query requests, one
    panel after the other, so that every program the window's panels run
    (the serve program of each bucket, and the batcher's concatenation,
    padding and slicing for that many requests) exists before the window
    opens."""
    for m in panels:
        futs = [server.submit(pool[i % len(pool)], top_n) for i in range(m)]
        for f in futs:
            f.result(timeout=timeout)
