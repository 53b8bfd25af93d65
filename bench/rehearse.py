#!/usr/bin/env python3
"""Compile rehearsal of each cell's serve program for a described TPU v5e,
without the chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

For every configuration in ``BENCHMARK.json`` and each panel bucket its
cells use, this builds the engine over an index of the configuration's
real size and format (zero codes: only shapes matter), lowers the engine's
whole-request program (``fused_encode`` -> scan-and-select -> top-n merge)
for one chip of a ``v5e:2x2`` topology, compiles it with the TPU compiler
and prints ``memory_analysis()``.  Nothing runs: this proves lowering,
tiling and fit, never results or speed.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
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
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        n, d, h, k = (int(cfg[x]) for x in ("n_rows", "d", "h", "k"))
        buckets = sorted({
            b for w in bench["workloads"] if w["config"] == c["name"]
            for b in _buckets(json.loads(
                (BENCH / "traffic" / f"{w['traffic']}.json").read_text()))})
        codes = SparseCodes(jnp.zeros((n, k), jnp.float32),
                            jnp.zeros((n, k), jnp.int32), h)
        index = build_index(codes, quantize=cfg["index_format"] == "q8")
        params = {"w_enc": jnp.zeros((d, h)), "b_enc": jnp.zeros((h,)),
                  "w_dec": jnp.zeros((h, d))}
        engine = RetrievalEngine(index, params,
                                 config=EngineConfig(use_kernel=True))
        arrays, _ = engine._serve_leaves()
        specs = [jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)
                 for v in arrays]
        top_n = 100
        for b in buckets:
            xb = jax.ShapeDtypeStruct((b, d), jnp.float32, sharding=one)
            compiled = engine._serve_fn(top_n).lower(specs, xb).compile()
            text = compiled.as_text()
            mem = compiled.memory_analysis()
            print(f"{c['name']} {path_name(engine)} bucket {b}: compiled "
                  f"for {topo.devices[0].device_kind}; Mosaic kernels "
                  f"{text.count('tpu_custom_call')}; arguments "
                  f"{mem.argument_size_in_bytes / 2**20:.1f} MiB, outputs "
                  f"{mem.output_size_in_bytes / 2**20:.3f} MiB, temp "
                  f"{mem.temp_size_in_bytes / 2**20:.1f} MiB", flush=True)
        del engine, index, codes
    return 0


def _buckets(traffic: dict) -> set[int]:
    """The panel buckets a traffic's warm-up panels land in (the batcher's
    default buckets 8/16/32/64)."""
    return {next(b for b in (8, 16, 32, 64) if b >= m)
            for m in traffic["warm_panels"]}


if __name__ == "__main__":
    sys.exit(main())
