#!/usr/bin/env python3
"""Compile rehearsal of each cell's serve program for a described TPU v5e,
without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

For every configuration in ``BENCHMARK.json`` and each panel bucket its
cells use, this builds the engine over an index of the configuration's
real size and format (zero codes: only shapes matter), lowers the engine's
whole-request program (``fused_encode`` -> scan-and-select -> top-n merge)
for one chip of a ``v5e:2x2`` topology, compiles it with the TPU compiler
and prints ``memory_analysis()``.  A configuration with ``shards`` > 1 is
lowered instead as the mesh engine's program over that many devices of
the topology, with the index given only as shapes, split on the ``cand``
axis; its ``memory_analysis()`` is what each device holds, one shard of
the index among it.  Nothing runs: this proves lowering, tiling and fit,
never results or speed.
"""
from __future__ import annotations

import json
import os
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.core import build_index
    from repro.core.types import SparseCodes
    from repro.kernels.fused_encode import ops as encode_ops
    from repro.kernels.sparse_dot import ops
    from repro.serving import EngineConfig, RetrievalEngine, path_name

    jax.config.update("jax_enable_compilation_cache", False)
    ops._on_tpu = encode_ops._on_tpu = lambda: True   # Mosaic, not interpret
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    fits = True
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        n, d, h, k = (int(cfg[x]) for x in ("n_rows", "d", "h", "k"))
        buckets = sorted({
            b for w in bench["workloads"] if w["config"] == c["name"]
            for b in _buckets(json.loads(
                (BENCH / "traffic" / f"{w['traffic']}.json").read_text()))})
        shards = int(cfg.get("shards", 1))
        # a sharded index is given as shapes: its engine is built over a
        # stand-in of a few rows a shard, and its program lowered for the
        # real row count
        rows = n if shards == 1 else shards * 1280
        codes = SparseCodes(jnp.zeros((rows, k), jnp.float32),
                            jnp.zeros((rows, k), jnp.int32), h)
        index = build_index(codes, quantize=cfg["index_format"] == "q8")
        params = {"w_enc": jnp.zeros((d, h)), "b_enc": jnp.zeros((h,)),
                  "w_dec": jnp.zeros((h, d))}
        mesh = (None if shards == 1 else
                Mesh(np.array(topo.devices[:shards]), ("cand",)))
        engine = RetrievalEngine(index, params, config=EngineConfig(
            use_kernel=True, mesh=mesh))
        arrays, _ = engine._serve_leaves()
        if mesh is None:
            specs = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
                     for v in arrays]
            where = one
            on = f"one chip of {topo.devices[0].device_kind}"
        else:
            specs = [jax.ShapeDtypeStruct(
                (n,) + v.shape[1:] if sh.spec == P("cand") else v.shape,
                v.dtype, sharding=sh)
                for v, sh in zip(arrays, engine._serve_shardings(arrays))]
            where = NamedSharding(mesh, P())
            on = (f"{shards} chips of {topo.devices[0].device_kind}, "
                  f"{n // shards} rows each; per device")
        top_n = 100
        for b in buckets:
            xb = jax.ShapeDtypeStruct((b, d), jnp.float32, sharding=where)
            try:
                compiled = engine._serve_fn(top_n).lower(specs, xb).compile()
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                held = sum(np.prod(a.sharding.shard_shape(a.shape))
                           * a.dtype.itemsize for a in [*specs, xb])
                print(f"{c['name']} {path_name(engine)} bucket {b}: does "
                      f"not fit {on}; arguments {held / 2**20:.1f} MiB by "
                      f"their shapes:", flush=True)
                print(_oom_report(str(e)), flush=True)
                fits = False
                continue
            text = compiled.as_text()
            mem = compiled.memory_analysis()
            print(f"{c['name']} {path_name(engine)} bucket {b}: compiled "
                  f"for {on}; Mosaic kernels "
                  f"{text.count('tpu_custom_call')}; arguments "
                  f"{mem.argument_size_in_bytes / 2**20:.1f} MiB, outputs "
                  f"{mem.output_size_in_bytes / 2**20:.3f} MiB, temp "
                  f"{mem.temp_size_in_bytes / 2**20:.1f} MiB", flush=True)
            if mesh is not None:
                print(f"  collectives: all-gather "
                      f"{text.count('all-gather')}, all-reduce "
                      f"{text.count('all-reduce')}", flush=True)
        del engine, index, codes
    return 0 if fits else 1


def _oom_report(text: str, largest: int = 4) -> str:
    """The compiler's account of a program that does not fit: the totals
    and its ``largest`` allocations (size, shape, label), one line each."""
    keep, allocs = [], 0
    for line in text.splitlines():
        line = re.sub(r"^\d+\.\s+", "", line.strip())   # "1. Size: ..."
        if line.startswith("Size:"):
            allocs += 1
            if allocs > largest:
                break
        if line.startswith(("jax.errors", "RESOURCE_EXHAUSTED", "Total hbm",
                            "reserved", "program", "arguments", "HLO temp",
                            "Size:", "Shape:", "Unpadded size:",
                            "XLA label:")):
            keep.append("  " + line.split(", backend_config")[0][:160])
    return "\n".join(keep)


def _buckets(traffic: dict) -> set[int]:
    """The panel buckets a traffic's warm-up panels land in (the batcher's
    default buckets 8/16/32/64)."""
    return {next(b for b in (8, 16, 32, 64) if b >= m)
            for m in traffic["warm_panels"]}


if __name__ == "__main__":
    sys.exit(main())
