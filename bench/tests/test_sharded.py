"""A configuration's ``shards``: the build puts each shard's rows on its
own device and gives the one-device index array for array; a tiny
four-shard cell runs correct on the sharded path; a four-device trace
charges each scan call for its own chip's rows; and the harness refuses,
before any device work, a layout the cell's chips cannot hold.  The
four-device cases run in a subprocess with four CPU devices."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if __name__ == "__main__":
    for p in (BENCH, ROOT / "src", ROOT):
        sys.path.insert(0, str(p))

from yardstick import counts  # noqa: E402
from yardstick.trace import Op, Span, Trace  # noqa: E402

MS = 1_000_000  # ns
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _in_four_devices(what: str) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, what], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return p.stdout.strip().splitlines()[-1]


def tiny_cfg(name="compresae-1m-f32", **kw):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update({"d": 64, "h": 256, "k": 8, "n_rows": 4096,
                "encode_chunk": 512, **kw})
    cfg["training"] = dict(cfg["training"], steps=3, batch=256)
    return cfg


# ----------------------------------------------------- per-chip counts
def _trace(devices: int, panels: int = 3, rows: int = 64):
    """``panels`` panels of ``rows`` rows; in each, every device runs one
    encode op (1 ms) and one scan op (20 ms on device i: 20 + i ms)."""
    ops, spans = [], [Span("bench.window", 0, 1000 * MS)]
    for p in range(panels):
        t0 = (10 + 100 * p) * MS
        spans.append(Span("bench.panel", t0, t0 + 60 * MS, {"rows": rows}))
        for i in range(devices):
            dev = f"/device:TPU:{i}"
            ops.append(Op("%fused_encode_pallas.1", t0 + MS, t0 + 2 * MS,
                          dev, "%fused_encode_pallas.1"))
            ops.append(Op("%fused_retrieve_sparse_q_pallas.1", t0 + 2 * MS,
                          t0 + (22 + i) * MS, dev,
                          "%fused_retrieve_sparse_q_pallas.1"))
    return Trace(ops=ops, spans=spans)


@pytest.mark.parametrize("shards", [1, 4])
def test_each_scan_call_is_charged_for_its_own_chips_rows(shards):
    n, b, k, h, d, top_n = 2 ** 22, 64, 32, 4096, 768, 100
    cfg = {"d": d, "h": h, "k": k, "n_rows": n, "index_format": "f32"}
    if shards > 1:
        cfg["shards"] = shards
    run = SimpleNamespace(trace=_trace(shards), config=cfg, counts=counts,
                          top_n=top_n, peak=PEAK)
    rows = n // shards
    assert counts.rows_per_chip(cfg) == rows
    # one chip's scan: its rows' codes (f32 values, int32 ids) and inverse
    # norms read once, 2*B*rows*k flops; the panel's codes in, top-n out
    scan = max(2 * b * rows * k / 197e12,
               (rows * (k * 8 + 4) + 8 * b * k + 8 * b * top_n) / 819e9)
    encode = max(2 * b * d * h / 197e12,
                 (4 * (b * d + d * h + h) + 8 * b * k) / 819e9)
    scan_s = 3 * sum(20 + i for i in range(shards)) * 1e-3
    assert _reader("scan_roofline_share")(run) == pytest.approx(
        100 * 3 * shards * scan / scan_s)
    assert _reader("scan_roofline_share.lone")(run) == pytest.approx(
        100 * 3 * shards * scan / scan_s)
    # every chip encodes the whole panel
    assert _reader("encode_roofline_share")(run) == pytest.approx(
        100 * encode / 1e-3)
    for name in ("serve_mfu", "serve_mfu.latency"):
        assert _reader(name)(run) == pytest.approx(
            100 * 3 * (encode + scan) / 1.0)


# ------------------------------------------------------------ refusals
@pytest.mark.parametrize("chips,n_rows,chunk,why", [
    (1, 4096, 512, "asks for 1 chip(s), its configuration splits the "
                   "catalog into 4 shards"),
    (4, 4096, 2048, "is not a whole number of encode_chunk 2048 chunks for "
                    "each of 4 shards"),
])
def test_run_refuses_a_layout_the_chips_cannot_hold(tmp_path, chips, n_rows,
                                                    chunk, why):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = tiny_cfg(n_rows=n_rows, encode_chunk=chunk, shards=4,
                   path="fp32-kernel-sharded")
    (tmp_path / "bench" / "configs" / "x4.json").write_text(json.dumps(cfg))
    bench["configs"].append(dict(bench["configs"][1], name="x4",
                                 file="bench/configs/x4.json"))
    bench["workloads"].append({"name": "x4.full", "config": "x4",
                               "traffic": "full", "chips": chips,
                               "why": "four shards"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "x4.full", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert why in p.stderr and "TPU" not in p.stderr


# ------------------------------------------------------- four devices
def test_sharded_build_puts_each_shard_on_its_device():
    assert _in_four_devices("build") == "ok build"


def test_tiny_four_shard_cell_is_correct_on_the_sharded_path():
    assert _in_four_devices("cell") == "ok cell"


def _build_four():
    import jax
    import numpy as np

    from repro.core import build_index
    from yardstick.catalog import Catalog, train_weights
    from yardstick.system import encode_catalog, shard_devices

    assert len(jax.local_devices()) == 4
    for fmt in ("q8", "f32"):
        one_cfg = tiny_cfg(index_format=fmt)
        four_cfg = dict(one_cfg, shards=4)
        n, k = one_cfg["n_rows"], one_cfg["k"]
        params = train_weights(Catalog(3, one_cfg), 3, one_cfg)
        four, mesh = encode_catalog(four_cfg, Catalog(3, four_cfg), params)
        _, devices = shard_devices(four_cfg)
        assert mesh is not None and len(set(devices)) == 4
        for a in (four.values, four.indices):
            assert len(a.addressable_shards) == 4
            for sh in a.addressable_shards:
                s = devices.index(sh.device)
                assert sh.index[0] == slice(s * n // 4, (s + 1) * n // 4)
                assert sh.data.shape == (n // 4, k)
        # no device holds more than its shard of codes
        for a in jax.live_arrays():
            if a.ndim == 2 and a.shape[1] == k:
                assert all(sh.data.shape[0] <= n // 4
                           for sh in a.addressable_shards), a.shape
        four_index = build_index(four, quantize=fmt == "q8")
        one, none = encode_catalog(one_cfg, Catalog(3, one_cfg), params)
        assert none is None
        one_index = build_index(one, quantize=fmt == "q8")
        assert four_index.checksum == one_index.checksum
        got = jax.tree.leaves(four_index)
        want = jax.tree.leaves(one_index)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
            if getattr(g, "ndim", 0) and g.shape[0] == n:
                assert {sh.data.shape[0] for sh in g.addressable_shards} \
                    == {n // 4}
        del four, four_index, one, one_index, got, want
    print("ok build")


def _cell_four():
    import run as harness

    cell = harness.Cell("compresae-1m-f32.full")
    cell.spec = dict(cell.spec, chips=4)
    cell.config = tiny_cfg(shards=4, path="fp32-kernel-sharded")
    cell.traffic.update(clients=16, warm_panels=[16], query_pool=64,
                        users=50)
    assert harness.layout_fault(cell) is None
    res = harness.measure(cell, 2 ** 32 + 5, 2.0, False, None,
                          use_kernel=True)
    checks = res["checks"]
    assert res["correct"], checks
    assert checks["unanswered"]["value"] == checks["off_rung"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] > 0
    print("ok cell")


if __name__ == "__main__":
    {"build": _build_four, "cell": _cell_four}[sys.argv[1]]()
