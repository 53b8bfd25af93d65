"""The system under test, built from what the benchmark generated.

The program makes the catalog codes (``fused_encode`` over the seeded
catalog, chunk by chunk, each shard's chunks on its own chip where the
configuration has ``shards``), the index (``build_index`` in the
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

from yardstick import counts


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


def shard_devices(cfg: dict):
    """(mesh, devices): the 1-D ``cand`` mesh a configuration's rows are
    split over and its devices, shard ``s`` on ``devices[s]``; (None,
    [None]) for one chip, whose rows stay on the default device."""
    from repro.launch.mesh import make_candidate_mesh

    shards = counts.shards(cfg)
    if shards == 1:
        return None, [None]
    mesh = make_candidate_mesh(shards)
    return mesh, list(mesh.devices.flat)


def encode_catalog(cfg: dict, catalog, params: dict):
    """(codes, mesh): the catalog's codes, made by the program's
    ``fused_encode`` chunk by chunk, and the mesh they are split over (None
    on one chip).  Chunk ``c`` belongs to shard ``c // (n_chunks /
    shards)``: it is made and encoded on that shard's device and
    concatenated there with the shard's other chunks, so no device ever
    holds more than its shard's codes and the dense catalog never exists
    whole.  At most two chunks a device are in flight."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.types import SparseCodes
    from repro.kernels.fused_encode import fused_encode

    k, h, n = int(cfg["k"]), int(cfg["h"]), catalog.n_rows
    mesh, devices = shard_devices(cfg)
    per = catalog.n_chunks // len(devices)
    enc = jax.jit(lambda x, w, b: tuple(fused_encode(x, w, b, k)[:2]))
    weights = [(params["w_enc"], params["b_enc"]) if d is None else
               jax.device_put((params["w_enc"], params["b_enc"]), d)
               for d in devices]
    parts = [([], []) for _ in devices]
    for j in range(per):
        for s, dev in enumerate(devices):
            v, i = enc(catalog.chunk_rows(s * per + j, dev), *weights[s])
            parts[s][0].append(v)
            parts[s][1].append(i)
        if j:
            jax.block_until_ready([p[0][j - 1] for p in parts])
    vals = [jnp.concatenate(v) for v, _ in parts]
    idx = [jnp.concatenate(i) for _, i in parts]
    del parts
    if mesh is None:
        return SparseCodes(values=vals[0], indices=idx[0], dim=h), None
    rows = NamedSharding(mesh, P("cand"))
    return SparseCodes(
        values=jax.make_array_from_single_device_arrays((n, k), rows, vals),
        indices=jax.make_array_from_single_device_arrays((n, k), rows, idx),
        dim=h), mesh


def build_engine(cfg: dict, catalog, params: dict, *, use_kernel="auto"):
    """Codes, index and guarded engine for one configuration, on a mesh of
    ``shards`` chips where the configuration has that key.  Raises when
    the engine would not serve on the configuration's path."""
    from repro.core import build_index
    from repro.serving import (
        EngineConfig, GuardedEngine, RetrievalEngine, path_name,
    )

    codes, mesh = encode_catalog(cfg, catalog, params)
    index = build_index(codes, quantize=cfg["index_format"] == "q8")
    del codes
    engine = RetrievalEngine(index, params, config=EngineConfig(
        use_kernel=use_kernel, mesh=mesh))
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
