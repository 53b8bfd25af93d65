"""Retrieval serving entry point — the paper's inference section as a
runnable service loop.

Builds a compressed index (CompresSAE codes + norms) over a catalog, then
constructs a ``repro.serving.RetrievalEngine`` — ONE object owning
(params, index, mode, backend, mesh) — and serves batched requests through
``engine.retrieve_dense(x, n)``: raw dense embeddings in, top-n out, the
whole encode→score→select chain under a single jit with no dense-query or
code round-trip through HBM (on TPU: fused_encode → fused_retrieve_sparse_q,
only (Q, k) codes and (Q, n) results touch HBM).  Modes:
  * sparse         — direct sparse-space cosine (fast path; sparse-query
                     kernel, codes scored as-is)
  * reconstructed  — kernel-trick scoring (high-fidelity path; dense
                     z = W_decᵀ(W_dec s_q) folded into the query prep)
and reports recall@n against exact dense retrieval plus latency stats,
including which backend path (fused Pallas kernels vs chunked jnp) served.

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --requests 20

Candidate-sharded serving (catalogs beyond one chip's HBM): ``--shards N``
shards the index along the candidate axis of an N-way mesh; the engine
replicates the prepped query (sparse mode: just the (Q, k) codes) into the
shard_map and merges per-shard top-n sets with one small all-gather —
bit-identical results to single-device serving:

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --shards 4

Quantized serving (``--quantized``): the index that lives in HBM is the
compound-compressed format itself — int8 values + int16/int32 indices +
fp32 per-row scales (~2.6x less index traffic than fp32 codes at k=32) —
streamed straight into the quantized fused-retrieve generation, which
dequantizes candidate tiles in VMEM.  Results are bit-identical to
serving the dequantized index; composes with ``--shards``:

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --quantized

Approximate int8 scoring (``--precision int8``, requires ``--quantized``):
candidate tiles are scored in int8×int8 with int32 accumulation
(generation 5) instead of being dequantized to f32 first — the quality
cost vs exact scoring is reported live as recall@n against the same
exact quantized engine (``repro.core.eval``), alongside the usual recall
against dense truth:

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --quantized --precision int8

Hardened serving (ISSUE 6): requests flow through a
``repro.serving.GuardedEngine`` — admission guards, an optional
per-request deadline, and the degradation ladder — and the ``[serve]``
line reports degraded/sanitized request counters.  ``--self-check``
verifies the index checksum and runs the canary batch before traffic;
``--inject-fault`` exercises one deterministic failure end to end
(``corrupt-index`` keeps a pristine fallback index so startup degrades
instead of dying; ``dead-shard``/``slow-shard`` need ``--shards > 1``):

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --self-check
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --quantized --self-check --inject-fault corrupt-index
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --shards 4 --inject-fault dead-shard

Two-stage retrieval (ISSUE 7 + 8, ``--two-stage``): stage 1 unions the
query's k posting lists from an inverted index over the latents into a
bounded candidate set (``--candidate-fraction`` of the catalog), stage 2
gathers those rows into (Q, budget) candidate panels in ONE batched
gather and re-ranks the whole panel through a single gather-aware fused
retrieve — sub-linear in catalog size, approximate (recall vs dense
truth reported as usual).  Stage 1 runs on device by default (one jitted
batched union, no per-query host loop); ``--stage1 host`` pins the
bit-identical NumPy oracle instead.  The guard ladder sheds a device
stage-1 fault to host stage 1, then to the exact single-stage scan
(postings corruption fails both stage-1 rungs, e.g. ``--inject-fault
corrupt-postings``; with ``--self-check`` it is already a typed startup
failure via the inverted-index checksum):

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --two-stage
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --two-stage --candidate-fraction 0.1
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --two-stage --stage1 host
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --two-stage --inject-fault corrupt-postings

Mutable serving (ISSUE 9, ``--mutate``): the engine serves a
``repro.core.segments.SegmentedIndex`` — the built index becomes the
immutable quantized base, and a deterministic add/delete/compact trace is
replayed through ``engine.apply_update`` before traffic: deletes fold into
the kernels' masking epilogue (fully-deleted tiles are skipped on device),
adds land in a small append-only delta segment served as an extra shard of
the same streaming top-n, and ``compact`` folds survivors into a fresh
base bit-identical to rebuilding from scratch.  Recall is reported against
dense truth over the SURVIVING catalog (deleted rows excluded, added rows
included).  ``--inject-fault corrupt-delta`` flips one bit in the delta
segment; the per-segment CRC catches it at startup and serving sheds to
base-only with the lost coverage reported:

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --mutate
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --quantized --mutate --self-check
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --quantized --mutate --inject-fault corrupt-delta

Microbatched loadtest (ISSUE 10, ``--loadtest``): instead of the fixed
recall loop, drive the same hardened stack through the microbatching
front (``repro.serving.MicrobatchServer``) with Zipfian closed-loop
traffic — concurrent single-row requests coalesced into BLOCK_Q-aligned
panels (``--max-wait-us`` bounds how long a lone request waits) — and
report latency percentiles, throughput, mean batch occupancy and shed
rate.  The full traffic-shaped benchmark driver (open-loop Poisson
arrivals, ``BENCH_serving.json``) is ``repro.launch.loadtest``:

    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --loadtest --requests 200
    PYTHONPATH=src python -m repro.launch.serve --catalog 50000 --quantized --loadtest --max-wait-us 500
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _force_host_devices_from_argv() -> None:
    """``--shards N`` on CPU needs N visible devices, and XLA only honours
    the device-count forcing flag before jax initializes — so peek at argv
    at module-import time, before the jax import below.  No-op when the
    flag is already present (e.g. under the tier-1 conftest) or on real
    multi-device backends."""
    n = None
    for i, tok in enumerate(sys.argv):
        try:
            if tok == "--shards":
                n = int(sys.argv[i + 1])
            elif tok.startswith("--shards="):
                n = int(tok.split("=", 1)[1])
        except (IndexError, ValueError):
            return
    if n is None:
        return
    flag = "xla_force_host_platform_device_count"
    if n > 1 and flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} --{flag}={n}"
        ).strip()


if __name__ == "__main__":
    _force_host_devices_from_argv()

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    SAEConfig,
    build_index,
    encode,
    init_train_state,
    score_dense,
    top_n,
    train_step,
)
from repro.core.retrieval import kernel_path
from repro.core.eval import recall_at_n, retrieval_quality
from repro.data import clustered_embeddings
from repro.errors import EngineConfigError
from repro.launch import use_compile_cache
from repro.optim import AdamConfig
from repro.serving import (
    FAULTS,
    EngineConfig,
    FaultInjector,
    GuardedEngine,
    RetrievalEngine,
    corrupt_postings,
    flip_delta_byte,
    flip_index_byte,
    poison_queries,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    EngineConfig.add_flags(ap)  # the shared engine-knob namespace
    ap.add_argument("--catalog", type=int, default=50000)
    ap.add_argument("--d", type=int, default=256)
    ap.add_argument("--h", type=int, default=1024)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--topn", type=int, default=20)
    ap.add_argument("--loadtest", action="store_true",
                    help="after building the (possibly hardened/mutated) "
                         "engine, drive it through the microbatching front "
                         "with Zipfian closed-loop traffic instead of the "
                         "fixed recall loop (see repro.launch.loadtest for "
                         "the full benchmark driver)")
    ap.add_argument("--max-wait-us", type=float, default=2000.0,
                    help="loadtest microbatch coalescing deadline for the "
                         "oldest queued request")
    ap.add_argument("--mutate", action="store_true",
                    help="serve a segmented mutable index: the built index "
                         "becomes the immutable base and a deterministic "
                         "add/delete/compact trace is replayed through "
                         "engine.apply_update before traffic (sparse mode, "
                         "unsharded, single-stage)")
    ap.add_argument("--self-check", action="store_true",
                    help="verify the index content checksum and run a "
                         "canary batch against the reference contract "
                         "before accepting traffic (typed error on failure)")
    ap.add_argument("--inject-fault", choices=FAULTS, default=None,
                    help="deterministically inject one serving fault and "
                         "serve through it (demonstrates the degradation "
                         "ladder; see repro.serving.faults)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline budget; slow paths are "
                         "abandoned when it expires and the response is "
                         "tagged deadline_exceeded (default: unbounded)")
    args = ap.parse_args(argv)
    use_compile_cache()
    # engine-knob cross checks (int8 vs quantized, two-stage vs shards,
    # stage1 vs two-stage, ...) live on EngineConfig now — one namespace,
    # one validator, every entry point
    try:
        engine_cfg = EngineConfig.from_flags(args)
    except EngineConfigError as e:
        ap.error(str(e))
    # serve-specific combinations stay here: fault fixtures and the
    # mutable-serving trace are this entry point's own surface
    if args.inject_fault in ("dead-shard", "slow-shard") and args.shards < 2:
        ap.error(f"--inject-fault {args.inject_fault} requires --shards > 1")
    if args.inject_fault == "corrupt-postings" and not args.two_stage:
        ap.error("--inject-fault corrupt-postings requires --two-stage "
                 "(the fault lives in stage 1's posting lists)")
    if args.mutate and (args.shards > 1 or args.two_stage
                        or args.mode != "sparse"):
        ap.error("--mutate requires --mode sparse, --shards 1 and no "
                 "--two-stage (the segmented index serves single-stage "
                 "sparse, unsharded)")
    if args.inject_fault == "corrupt-delta" and not args.mutate:
        ap.error("--inject-fault corrupt-delta requires --mutate "
                 "(the fault lives in the segmented index's delta)")

    path = ("fused-kernel" if kernel_path(engine_cfg.use_kernel)
            else "jnp-chunked")
    mesh = engine_cfg.mesh
    if mesh is not None:
        path = f"{path}+sharded"

    cfg = SAEConfig(d=args.d, h=args.h, k=args.k)
    catalog = clustered_embeddings(jax.random.PRNGKey(0), args.catalog, d=cfg.d)

    print(f"[index] training CompresSAE ({cfg.d}->{cfg.h}, k={cfg.k}) "
          f"on {args.catalog} embeddings")
    state = init_train_state(cfg, jax.random.PRNGKey(1))
    step = jax.jit(lambda s, b: train_step(s, b, cfg, AdamConfig(lr=3e-3)))
    for i in range(args.train_steps):
        idx = jax.random.randint(
            jax.random.PRNGKey(100 + i), (min(8192, args.catalog),), 0, args.catalog
        )
        state, m = step(state, catalog[idx])
    print(f"[index] final cos loss {float(m['loss']):.4f}")

    codes = encode(state.params, catalog, cfg.k)
    index = build_index(codes, state.params, quantize=args.quantized)
    dense_bytes = args.catalog * cfg.d * 4
    sparse_bytes = codes.nbytes_logical
    print(f"[index] dense {dense_bytes/2**20:.1f} MiB -> compressed "
          f"{sparse_bytes/2**20:.1f} MiB ({dense_bytes/sparse_bytes:.1f}x)")
    if args.quantized:
        q_bytes = index.codes.nbytes_logical
        path = f"{path}+quantized"
        print(f"[index] serving format: int8/{index.codes.indices.dtype} "
              f"{q_bytes/2**20:.2f} MiB in HBM "
              f"({100 * q_bytes / sparse_bytes:.0f}% of the fp32 codes, "
              f"{dense_bytes/q_bytes:.1f}x vs dense)")

    if args.precision == "int8":
        path = f"{path}+int8"
    if args.two_stage:
        stage1_impl = "device" if args.stage1 == "auto" else args.stage1
        path = f"{path}+two-stage-{stage1_impl}"

    # ------------------------------------------------ hardened serving setup
    fallback_index = None
    if args.inject_fault == "corrupt-index":
        # serve the corrupted bytes, keep the pristine build as the
        # verified fallback replica — startup must catch the flip by
        # checksum and degrade onto the fallback instead of dying
        fallback_index, index = index, flip_index_byte(index, byte=17, bit=2)
        args.self_check = True
        print("[faults] corrupt-index: flipped one bit in the served "
              "index; pristine fallback retained")
    injector = None
    if args.inject_fault in ("dead-shard", "slow-shard", "kernel-exception"):
        injector = FaultInjector(args.inject_fault, shard=0)
        print(f"[faults] injecting {args.inject_fault} "
              f"(deterministic, shard 0)")

    serve_index = index
    if args.mutate:
        from repro.core.segments import SegmentedIndex

        serve_index = SegmentedIndex.from_index(index)
        path = f"{path}+segmented"

    engine = RetrievalEngine(serve_index, state.params, config=engine_cfg)
    if args.inject_fault == "corrupt-postings":
        # plant out-of-range ids in the posting lists AFTER the build:
        # stage 1's integrity check must trip on every request, and the
        # ladder must re-serve each one on the exact single-stage rung
        engine.inverted = corrupt_postings(engine.inverted)
        print("[faults] corrupt-postings: planted out-of-range ids in "
              "every posting list; expecting per-request fallback to "
              "single-stage")

    # ----------------------------------------------- mutable serving trace
    all_emb, surv_ids, surv_emb = catalog, None, None
    if args.mutate:
        n0 = args.catalog
        new_emb = clustered_embeddings(jax.random.PRNGKey(77), 24, d=cfg.d)
        all_emb = jnp.concatenate([catalog, new_emb], axis=0)
        new_codes = encode(state.params, new_emb, cfg.k)

        def _rows(c, lo, hi):
            return c._replace(values=c.values[lo:hi],
                              indices=c.indices[lo:hi])

        del0 = sorted({int(v) for v in
                       np.linspace(0, n0 - 1, 7).astype(np.int64)})
        engine.apply_update("delete", ids=del0)
        engine.apply_update("add", codes=_rows(new_codes, 0, 16),
                            ids=list(range(n0, n0 + 16)))
        engine.apply_update("delete", ids=[n0 + 3, n0 + 11])
        engine.apply_update("compact")
        engine.apply_update("add", codes=_rows(new_codes, 16, 24),
                            ids=list(range(n0 + 16, n0 + 24)))
        more = [int(v) for v in np.asarray(engine.segments.alive_ids())
                if int(v) < n0][:3]
        engine.apply_update("delete", ids=more)
        seg = engine.segments
        n_del = len(del0) + 2 + len(more)
        print(f"[mutate] trace replayed through apply_update: "
              f"{n0} base rows, +24 added, -{n_del} deleted, 1 compaction "
              f"-> {seg.n_alive} alive "
              f"(base coverage {seg.base_coverage:.3f})")
        # dense truth for recall is the SURVIVING catalog: deleted rows
        # excluded, added rows included, positions translated to item ids
        surv = np.asarray(seg.alive_ids())
        surv_ids = jnp.asarray(surv)
        surv_emb = jnp.asarray(np.asarray(all_emb)[surv])
        if args.inject_fault == "corrupt-delta":
            engine = RetrievalEngine(flip_delta_byte(seg), state.params,
                                     config=engine_cfg)
            args.self_check = True
            print("[faults] corrupt-delta: flipped one bit in the delta "
                  "segment; expecting the per-segment CRC to catch it at "
                  "startup and serving to shed to base-only")

    guard = GuardedEngine(
        engine,
        deadline_ms=args.deadline_ms,
        on_invalid=("sanitize" if args.inject_fault == "nonfinite-query"
                    else "reject"),
        injector=injector,
        fallback_index=fallback_index,
        run_self_check=args.self_check,
    )
    if guard.self_check_report is not None:
        rep = guard.self_check_report
        print(f"[self-check] index checksum verified; canary "
              f"{rep.canary_q}x top-{rep.canary_n} on {rep.path} ok "
              f"(kernel-vs-ref: {rep.kernel_vs_ref or 'same path'}, "
              f"max |Δscore| {rep.max_abs_diff:.2e})")
    if guard.degraded_from_start:
        print(f"[self-check] DEGRADED: {guard.degraded_from_start}")
        engine = guard.engine  # the fallback-backed engine now serves

    # --------------------------------------------- microbatched loadtest
    if args.loadtest:
        # same hardened stack, but traffic-shaped: Zipfian single-row
        # requests coalesced into BLOCK_Q panels by the microbatch front
        from repro.data import ZipfianQueryStream
        from repro.launch.loadtest import run_closed_loop, summarize
        from repro.serving import MicrobatchServer

        users = np.asarray(
            clustered_embeddings(jax.random.PRNGKey(7), 2000, d=cfg.d))
        stream = ZipfianQueryStream(users, seed=0)
        _, queries = stream.sample(max(args.requests, 1))
        with MicrobatchServer(guard,
                              max_wait_us=args.max_wait_us) as server:
            server.warmup(args.topn)
            result = run_closed_loop(server, queries, concurrency=16,
                                     topn=args.topn)
            rec = summarize(result, server, extra={"config": path})
        print(f"[serve] loadtest path={','.join(rec['paths_seen'])} "
              f"config={path} closed-loop "
              f"{rec['requests']} requests: "
              f"p50 {rec['p50_ms']:.1f} ms p95 {rec['p95_ms']:.1f} ms "
              f"p99 {rec['p99_ms']:.1f} ms | "
              f"{rec['throughput_rps']:.0f} rps, "
              f"occupancy {rec['occupancy_mean']:.2f}, "
              f"shed {rec['shed_rate']:.3f}, panels {rec['panels']}, "
              f"serve traces {guard.engine.counters['serve_traces']}")
        return _exit_code(args, rec["degraded"])

    # int8 scoring is approximate: measure its live quality against the
    # SAME engine at exact precision (the harness's reference path)
    exact_engine = None
    if args.precision == "int8" and guard.engine.precision == "int8":
        seg_now = getattr(guard.engine, "segments", None)
        exact_engine = RetrievalEngine(
            seg_now if seg_now is not None else guard.engine.index,
            state.params,
            config=engine_cfg.replace(
                precision="exact", stage="single", stage1="auto",
                mesh=None if seg_now is not None else mesh,
            ),
        )

    lat, recalls, vs_exact, answered = [], [], [], set()
    for r in range(args.requests):
        q = clustered_embeddings(jax.random.PRNGKey(1000 + r), args.batch, d=cfg.d)
        if args.inject_fault == "nonfinite-query":
            q = poison_queries(q, kind="nan" if r % 2 == 0 else "inf",
                               position=(r % args.batch, r % cfg.d))
        t0 = time.time()
        vals, ids, status, *_ = guard.retrieve_dense(q, args.topn)
        jax.block_until_ready(ids)
        lat.append(time.time() - t0)
        answered.add(status.path)
        if status.degraded and r < 3:
            print(f"[guard] request {r} degraded -> {status.path} "
                  f"({status.fault})")
        if args.mutate:
            _, pos = top_n(score_dense(surv_emb, q), args.topn)
            true_ids = jnp.take(surv_ids, pos)
        else:
            _, true_ids = top_n(score_dense(catalog, q), args.topn)
        recalls.append(recall_at_n(ids, true_ids))
        if exact_engine is not None:
            exact = exact_engine.retrieve_dense(q, args.topn)
            vs_exact.append(retrieval_quality((vals, ids), exact)["recall"])
    lat_ms = np.array(lat[1:]) * 1e3  # drop compile step
    quality = (f"int8-vs-exact recall@{args.topn} {np.mean(vs_exact):.3f} "
               if vs_exact else "")
    c = guard.counters
    guard_stats = (f"degraded {c['degraded']}/{c['requests']} "
                   f"sanitized {c['sanitized']} rejected {c['rejected']} ")
    two_stage_stats = (f"cand_frac {args.candidate_fraction:g} "
                       if args.two_stage else "")
    prefix = (f"[serve] mode={args.mode} path={','.join(sorted(answered))} "
              f"config={path} shards={args.shards} "
              f"{two_stage_stats}"
              f"recall@{args.topn} {np.mean(recalls):.3f} {quality}"
              f"{guard_stats}| ")
    if lat_ms.size:
        print(prefix +
              f"latency p50 {np.percentile(lat_ms, 50):.1f} ms "
              f"p99 {np.percentile(lat_ms, 99):.1f} ms over {args.requests} requests")
    else:
        # a single request is all compile: percentiles over zero steady-state
        # samples would raise — report the compile+first-request time instead
        print(prefix +
              f"compile+first-request {lat[0] * 1e3:.1f} ms "
              "(1 request; no steady-state latency percentiles)")
    return _exit_code(args, c["degraded"])


def _exit_code(args, degraded: int) -> int:
    """Non-zero when a response degraded although no fault was injected:
    the configured path could not serve, and the ladder hid it."""
    if degraded and args.inject_fault is None:
        print(f"[serve] FAIL: {degraded} response(s) degraded with no "
              "fault injected", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
