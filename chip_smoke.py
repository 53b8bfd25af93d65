#!/usr/bin/env python3
"""Bring-up check of the serving main path on a TPU, at the paper's widths.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # candidate-sharded engine, 4 chips

One chip: train the CompresSAE (d=768, h=4096, k=32) a few steps, encode a
seeded catalog of 2^20 rows on the device with the fused_encode kernel,
build the fp32 and the quantized index, and serve requests through
``MicrobatchServer -> GuardedEngine(run_self_check=True) ->
RetrievalEngine`` on the fused-kernel path.  Every response must be served
at ladder step 0, not degraded, and agree with a ``use_kernel=False``
engine on the same index: f32 scores allclose, ids identical away from
ties.  Recall against dense truth, compile seconds and per-request
milliseconds (set-up figures, not measurements) go on earlier lines.

``--four-chips`` runs only the sharded phase: 2^22 fp32 codes, encoded in
chunks without keeping the dense catalog, served by an engine over a
4-way ``cand`` mesh and by a one-chip engine; scores and ids must be
bit-identical.

The last line of stdout is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
the script exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
N_CATALOG = 2 ** 20
N_SHARDED = 2 ** 22
TRAIN_STEPS, TRAIN_BATCH = 50, 8192
ENCODE_CHUNK = 2 ** 16


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg) -> None:
    """A phase's verdict: raise (never ``assert``, which ``-O`` strips)."""
    if not ok:
        raise RuntimeError(msg() if callable(msg) else msg)


def require_tpu(count: int = 1) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < count:
        print(f"chip_smoke: needs {count} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def train_sae(cfg, catalog_rows, steps: int = TRAIN_STEPS):
    """A few optimizer steps on seeded catalog rows; returns params."""
    import jax

    from repro.core import init_train_state, train_step
    from repro.optim import AdamConfig

    state = init_train_state(cfg, jax.random.PRNGKey(SEED + 1))
    step = jax.jit(lambda s, b: train_step(s, b, cfg, AdamConfig(lr=3e-3)))
    t0 = time.perf_counter()
    for i in range(steps):
        rows = jax.random.randint(jax.random.PRNGKey(100 + i),
                                  (min(TRAIN_BATCH, catalog_rows.shape[0]),),
                                  0, catalog_rows.shape[0])
        state, metrics = step(state, catalog_rows[rows])
    loss = float(metrics["loss"])
    log(f"[train] {steps} steps of {min(TRAIN_BATCH, catalog_rows.shape[0])} "
        f"rows, loss {loss:.4f}, {time.perf_counter() - t0:.1f} s with "
        "compile")
    check(np.isfinite(loss), f"training loss {loss}")
    return state.params


def encode_catalog(params, k: int, chunks):
    """fused_encode over an iterable of (rows, d) chunks -> SparseCodes."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import SparseCodes
    from repro.kernels.fused_encode import fused_encode

    enc = jax.jit(lambda x: fused_encode(x, params["w_enc"],
                                         params["b_enc"], k))
    vals, idx = [], []
    t0 = time.perf_counter()
    for x in chunks:
        c = enc(x)
        vals.append(c.values)
        idx.append(c.indices)
    codes = SparseCodes(values=jnp.concatenate(vals),
                        indices=jnp.concatenate(idx),
                        dim=params["w_enc"].shape[1])
    jax.block_until_ready(codes.values)
    log(f"[encode] {codes.n} rows -> ({codes.n}, {k}) codes on the device "
        f"in {time.perf_counter() - t0:.1f} s with compile")
    return codes


def check_encode_kernel(params, x, k: int) -> None:
    """fused_encode against the jnp encoder on the same rows: the selected
    latent sets must agree (up to near-ties at the k-th value)."""
    from repro.core import encode
    from repro.kernels.fused_encode import fused_encode

    a = fused_encode(x, params["w_enc"], params["b_enc"], k)
    b = encode(params, x, k)
    ai, bi = np.asarray(a.indices), np.asarray(b.indices)
    overlap = np.mean([len(set(r) & set(s)) / k for r, s in zip(ai, bi)])
    log(f"[encode] kernel vs jnp encoder on {x.shape[0]} rows: latent-set "
        f"overlap {overlap:.5f}")
    check(overlap >= 0.999, f"encode kernel latent-set overlap {overlap}")


def assert_matches_ref(name, got_v, got_i, ref_v, ref_i) -> None:
    """The exact-path contract: f32 scores allclose, ids identical except
    where the reference itself has a near-tie at that rank."""
    got_v, ref_v = np.asarray(got_v), np.asarray(ref_v)
    got_i, ref_i = np.asarray(got_i), np.asarray(ref_i)
    np.testing.assert_allclose(got_v, ref_v, rtol=1e-5, atol=1e-6,
                               err_msg=name)
    step = ref_v[:, :-1] - ref_v[:, 1:]          # score gap to the next rank
    tol = 1e-5 * np.maximum(np.abs(ref_v[:, 1:]), 1.0)
    tied = np.zeros(ref_v.shape, bool)
    tied[:, 1:] |= step <= tol
    tied[:, :-1] |= step <= tol
    tied[:, -1] = True  # the next rank is unseen: the last slot may tie it
    bad = (got_i != ref_i) & ~tied
    check(not bad.any(), lambda: (
        f"{name}: {int(bad.sum())} ids differ from the reference away from "
        f"ties (first at {np.argwhere(bad)[0].tolist()})"))
    log(f"[check] {name}: kernel vs ref max |dscore| "
        f"{float(np.max(np.abs(got_v - ref_v))):.3e}, ids equal at "
        f"{float(np.mean(got_i == ref_i)):.5f} of ranks")


def serve_through_stack(engine, requests, n: int):
    """Serve each request through MicrobatchServer -> GuardedEngine
    (startup self-check on) -> engine; returns (responses, timings)."""
    import jax

    from repro.serving import GuardedEngine, MicrobatchServer

    t0 = time.perf_counter()
    guard = GuardedEngine(engine, run_self_check=True)
    rep = guard.self_check_report
    t_check = time.perf_counter() - t0
    log(f"[self-check] {rep.path}: kernel-vs-ref {rep.kernel_vs_ref}, "
        f"max |dscore| {rep.max_abs_diff:.2e}, {t_check:.1f} s with compile")
    with MicrobatchServer(guard) as server:
        t0 = time.perf_counter()
        server.warmup(n)
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        futures = [server.submit(x, n) for x in requests]
        responses = [f.result(timeout=600) for f in futures]
        jax.block_until_ready([r.ids for r in responses])
        wall = time.perf_counter() - t0
        stats = server.stats()
    log(f"[serve] warmup of {len(server.buckets)} panel buckets "
        f"{t_compile:.1f} s (compile); {len(requests)} requests in "
        f"{stats['panels']} panels, {1e3 * wall / len(requests):.1f} ms "
        "per request (set-up figure, not a measurement)")
    for i, r in enumerate(responses):
        s = r.status
        check(s.step == 0 and not s.degraded, f"request {i}: {s}")
    return responses, {"self_check_s": t_check, "compile_s": t_compile}


def stack_rows(responses):
    v = np.concatenate([np.atleast_2d(np.asarray(r.scores)) for r in responses])
    i = np.concatenate([np.atleast_2d(np.asarray(r.ids)) for r in responses])
    return v, i


def report_merge_stats(name, index, params, queries, k: int, n: int) -> None:
    """The scan kernel's merge counters on the served path's panels: the
    share of (panel, tile) steps that ran the top-n merge and the
    insertion rounds per merged tile, for full 8-row panels and for one
    request padded to a panel with zero rows as the batcher pads it."""
    import jax.numpy as jnp

    from repro.core.quantized_codes import QuantizedCodes
    from repro.kernels.fused_encode import fused_encode
    from repro.kernels.sparse_dot.kernel import BLOCK_N, BLOCK_Q
    from repro.serving.engine import mode_inv_norms, select_retrieve_fn

    c = index.codes
    quantized = isinstance(c, QuantizedCodes)
    fn = select_retrieve_fn(sparse_query=True, quantized=quantized,
                            int8_scoring=False, use_fused=True)
    cand = ((c.q_values, c.indices, c.scales) if quantized
            else (c.values, c.indices))
    lone = jnp.zeros((BLOCK_Q, queries.shape[1]), queries.dtype)
    tiles = -(-c.n // BLOCK_N)
    for label, x in (("full panels", queries),
                     ("lone", lone.at[0].set(queries[0]))):
        q = fused_encode(x, params["w_enc"], params["b_enc"], k)
        *_, stats = fn(*cand, mode_inv_norms(index, "sparse"), q.values,
                       q.indices, c.dim, n=n, with_stats=True)
        merged, rounds = np.asarray(stats).sum(axis=0)
        log(f"[merge] {name} {label}: {merged} of {len(stats) * tiles} "
            f"panel tiles merged ({merged / (len(stats) * tiles):.4f}), "
            f"{rounds / max(merged, 1):.2f} rounds per merged tile")


def one_chip() -> None:
    import jax

    from repro.core import SAEConfig, build_index, score_dense, top_n
    from repro.core.eval import recall_at_n
    from repro.data import clustered_embeddings
    from repro.models.registry import TOP_N
    from repro.serving import EngineConfig, RetrievalEngine, path_name

    cfg = SAEConfig()
    gen = jax.jit(clustered_embeddings, static_argnums=(1, 2))
    catalog = gen(jax.random.PRNGKey(SEED), N_CATALOG, cfg.d)
    log(f"[catalog] {N_CATALOG} x {cfg.d} f32 on the device "
        f"({catalog.nbytes / 2 ** 30:.2f} GiB)")
    params = train_sae(cfg, catalog)
    check_encode_kernel(params, catalog[:4096], cfg.k)
    codes = encode_catalog(
        params, cfg.k,
        (catalog[i:i + ENCODE_CHUNK] for i in range(0, N_CATALOG,
                                                     ENCODE_CHUNK)))

    # requests: 16 single rows (coalesced by the batcher) + two 8-row
    # batches; dense truth over the full catalog, then the catalog goes
    queries = gen(jax.random.PRNGKey(SEED + 7), 32, cfg.d)
    requests = [queries[i] for i in range(16)] + [queries[16:24],
                                                  queries[24:32]]
    _, truth = top_n(score_dense(catalog, queries), TOP_N)
    truth = np.asarray(truth)
    del catalog

    for quantize in (False, True):
        index = build_index(codes, quantize=quantize)
        mib = sum(getattr(a, "nbytes", 0)
                  for a in jax.tree.leaves(index.codes)) / 2 ** 20
        engine = RetrievalEngine(index, params,
                                 config=EngineConfig(use_kernel="auto"))
        name = path_name(engine)
        log(f"[index] {name}: {index.codes.n} rows, codes {mib:.0f} MiB")
        check(engine.use_fused, "use_kernel='auto' did not pick the kernels")
        responses, _ = serve_through_stack(engine, requests, TOP_N)
        paths = {r.status.path for r in responses}
        check(paths == {name}, f"served on {paths}, configured {name}")
        got_v, got_i = stack_rows(responses)
        ref = RetrievalEngine(index, params,
                              config=EngineConfig(use_kernel=False))
        ref_v, ref_i, *_ = ref.retrieve_dense(queries, TOP_N)
        assert_matches_ref(name, got_v, got_i, ref_v, ref_i)
        report_merge_stats(name, index, params, queries, cfg.k, TOP_N)
        log(f"[quality] {name}: recall@{TOP_N} vs dense truth "
            f"{recall_at_n(got_i, truth):.4f}")
        del engine, ref, index, responses


def four_chips() -> None:
    import jax

    from repro.core import SAEConfig, build_index
    from repro.data import clustered_embeddings
    from repro.launch.mesh import make_candidate_mesh
    from repro.models.registry import TOP_N
    from repro.serving import (
        EngineConfig, GuardedEngine, MicrobatchServer, RetrievalEngine,
    )

    cfg = SAEConfig()
    gen = jax.jit(clustered_embeddings, static_argnums=(1, 2))
    params = train_sae(cfg, gen(jax.random.PRNGKey(SEED), TRAIN_BATCH, cfg.d))
    # each chunk is made, encoded and dropped: the dense catalog never
    # exists whole, only its (N, k) codes
    chunks = (gen(jax.random.fold_in(jax.random.PRNGKey(SEED), c),
                  ENCODE_CHUNK, cfg.d)
              for c in range(N_SHARDED // ENCODE_CHUNK))
    index = build_index(encode_catalog(params, cfg.k, chunks))
    queries = gen(jax.random.PRNGKey(SEED + 7), 16, cfg.d)

    mesh = make_candidate_mesh(4)
    sharded = RetrievalEngine(index, params,
                              config=EngineConfig(use_kernel="auto",
                                                  mesh=mesh))
    check(sharded.use_fused, "use_kernel='auto' did not pick the kernels")
    responses, _ = serve_through_stack(sharded, [queries[:8], queries[8:]],
                                       TOP_N)
    paths = {r.status.path for r in responses}
    check(paths == {"fp32-kernel-sharded"}, f"served on {paths}")
    got_v, got_i = stack_rows(responses)

    single = RetrievalEngine(index, params,
                             config=EngineConfig(use_kernel="auto"))
    t0 = time.perf_counter()
    with MicrobatchServer(GuardedEngine(single)) as server:
        one = [server.serve(q, TOP_N, timeout=600)
               for q in (queries[:8], queries[8:])]
    log(f"[serve] one-chip engine, same requests: "
        f"{time.perf_counter() - t0:.1f} s with compile")
    one_v, one_i = stack_rows(one)
    check(np.array_equal(got_i, one_i), "sharded ids differ from one chip")
    check(np.array_equal(got_v, one_v),
          "sharded scores differ from one chip")
    log(f"[check] 4-chip sharded vs one chip over {index.codes.n} fp32 "
        f"codes: scores and ids bit-identical ({got_i.size} results)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip candidate-sharded phase")
    args = ap.parse_args(argv)
    device = require_tpu(4 if args.four_chips else 1)
    from repro.launch import use_compile_cache

    log(f"[device] {device} compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    four_chips() if args.four_chips else one_chip()
    log(f"[done] {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
