#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` at the root of the checkout (see
``bench/README.md``).  A run makes everything from ``--seed`` on the chip
(catalog, encoder weights, query stream), has the program build its index
and serving stack, warms up the panels the traffic sends, measures for
``--seconds``, then checks a seeded sample of the window's answers against
the plain reference in ``bench/yardstick/reference.py``.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  The run
refuses to start (exit 2, no result line) unless JAX sees as many TPU chips
as the cell asks for, of a kind in ``bench/peaks.json``, and (exit 2, before
any device work) where the cell's chips cannot hold its configuration's
``shards``.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
SAMPLE = 256          # answers checked per run, drawn from the seed
DRAIN_S = 60.0        # how long past the window an answer may still come


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    def __init__(self, name: str, root: Path = ROOT):
        bench = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
        self.spec = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(root / configs[self.spec["config"]]["file"])
        self.traffic = load_json(
            root / "bench" / "traffic" / f"{self.spec['traffic']}.json")
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._has(m)]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]


def layout_fault(cell: Cell) -> str | None:
    """Why the cell's chips cannot hold its configuration's shards, or None.
    A configuration's ``shards`` (1 where it has none) splits the catalog's
    rows evenly over that many chips, whole ``encode_chunk`` chunks a
    shard."""
    cfg = cell.config
    shards, chips = int(cfg.get("shards", 1)), int(cell.spec["chips"])
    n, chunk = int(cfg["n_rows"]), int(cfg["encode_chunk"])
    if shards < 1:
        return f"shards {shards} is not a positive count"
    if chips < shards:
        return (f"the cell asks for {chips} chip(s), its configuration "
                f"splits the catalog into {shards} shards")
    if n % chunk or (n // chunk) % shards:
        return (f"n_rows {n} is not a whole number of encode_chunk {chunk} "
                f"chunks for each of {shards} shards")
    return None


def require_chip(chips: int, peaks: dict) -> dict:
    """The device block of the result line; exits 2 without a TPU, with
    fewer chips than the cell asks for, or on a kind with no peaks."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        log(f"bench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {dev.platform} device(s)")
        sys.exit(2)
    if dev.device_kind not in peaks:
        log(f"bench: no peaks for device kind {dev.device_kind!r} in "
            f"bench/peaks.json")
        sys.exit(2)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


class CompileCounter:
    """Records when programs are lowered and compiled, to count those inside
    the window."""

    def __init__(self):
        import jax

        self.lowered: list[float] = []
        self.compiled: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered.append(time.monotonic())
        elif event.endswith("backend_compile_duration"):
            self.compiled.append(time.monotonic())

    def between(self, t0: float, t1: float) -> tuple[int, int]:
        return (sum(t0 <= t <= t1 for t in self.lowered),
                sum(t0 <= t <= t1 for t in self.compiled))


class GcPauses:
    """Records the interpreter's garbage collections: each one stops every
    thread, the batcher's too."""

    def __init__(self):
        self.pauses: list[tuple[int, float, float]] = []  # (gen, at, s)
        self._start = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.monotonic()
        else:
            now = time.monotonic()
            self.pauses.append((info["generation"], now, now - self._start))

    def summary(self, t0: float, t1: float) -> str:
        inside = [p for p in self.pauses if t0 <= p[1] <= t1]
        full = [s for g, _, s in inside if g == 2]
        return (f"garbage collections in the window {len(inside)}, "
                f"{sum(s for _, _, s in inside) * 1e3:.1f} ms in all; of "
                f"the oldest generation {len(full)}, longest "
                f"{max(full, default=0.0) * 1e3:.1f} ms")


def panel_pauses(panels: list, t0: float) -> str:
    """The longest panel and the longest pause between two panels, with
    when each began in the window: where a run lost its time."""
    if len(panels) < 2:
        return "fewer than two panels"
    span = max(panels, key=lambda p: p[1] - p[0])
    gap, at = max((b[0] - a[1], a[1]) for a, b in zip(panels, panels[1:]))
    return (f"longest panel {(span[1] - span[0]) * 1e3:.1f} ms at "
            f"{span[0] - t0:+.2f} s; longest pause between panels "
            f"{gap * 1e3:.1f} ms at {at - t0:+.2f} s")


class Run:
    """What the metric readers in ``bench/metrics`` read."""

    def __init__(self, cell: Cell, peak: dict, window, trace,
                 setup_s: float):
        from yardstick import counts

        self.cell, self.config, self.traffic = cell, cell.config, cell.traffic
        self.peak, self.window = peak, window
        self.trace, self.setup_s, self.counts = trace, setup_s, counts
        self.top_n = int(cell.traffic["top_n"])


class Context:
    """What a traffic driver gets: the server, the query pool and the
    traffic parameters."""

    def __init__(self, server, pool, traffic, seed, queue_full):
        self.server, self.pool, self.traffic = server, pool, traffic
        self.seed, self.queue_full = seed, queue_full
        self.top_n = int(traffic["top_n"])
        self.drain_s = DRAIN_S


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            device: dict | None, use_kernel="auto") -> dict:
    """Set up, run the window, reduce, check; returns the result line."""
    import jax
    import numpy as np

    from repro.errors import QueueFullError
    from repro.serving import MicrobatchServer
    from yardstick import trace as trace_mod
    from yardstick.catalog import Catalog, ZipfStream, train_weights
    from yardstick.reference import Reference, compare
    from yardstick.system import (
        PanelSpan, build_engine, query_pool, warm_up,
    )
    from yardstick.window import Window

    cfg, traffic = cell.config, cell.traffic
    peaks = load_json(BENCH / "peaks.json")
    peak = peaks[device["kind"]] if device else None
    top_n = int(traffic["top_n"])
    counter = CompileCounter()
    collections = GcPauses()

    # ------------------------------------------------------------ set-up
    stamps = [("start", time.monotonic())]
    data_seed = int(cfg["data_seed"])
    catalog = Catalog(data_seed, cfg)
    params = train_weights(catalog, data_seed, cfg)
    stamps.append(("weights", time.monotonic()))
    guard = build_engine(cfg, catalog, params, use_kernel=use_kernel)
    stamps.append(("codes, index, self-check", time.monotonic()))
    stream = ZipfStream(catalog.users(data_seed, int(traffic["users"])),
                        zipf_a=float(traffic["zipf_a"]),
                        jitter=float(traffic["jitter"]), seed=seed)
    _, queries = stream.sample(int(traffic["query_pool"]))
    pool = query_pool(queries)
    stamps.append(("queries", time.monotonic()))
    proxy = PanelSpan(guard)
    server = MicrobatchServer(proxy)
    warm_up(server, pool, top_n, traffic["warm_panels"], DRAIN_S)
    proxy.panels.clear()
    stamps.append(("warm-up", time.monotonic()))
    log("[setup] imports and device " + f"{stamps[0][1] - T_PROCESS:.2f} s; "
        + "; ".join(f"{n} {t - stamps[i][1]:.2f} s"
                    for i, (n, t) in enumerate(stamps[1:])))
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    window = Window(seconds, cfg["path"])
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    # ------------------------------------------------------------ window
    setup_s = time.monotonic() - T_PROCESS
    try:
        driver.run(Context(server, pool, traffic, seed, QueueFullError),
                   window)
    finally:
        if trace:
            jax.profiler.stop_trace()
    lowered, compiled = counter.between(window.t0, window.t1)
    late = counter.between(window.t1, time.monotonic())
    log(f"[window] {window.seconds:.0f} s: {len(window.sent())} requests "
        f"due, {len(window.requests) - len(window.sent())} sent after it; "
        f"{len(proxy.panels)} panels in all; programs lowered in the window "
        f"{lowered}, compiled {compiled} (after it: {late[0]}, {late[1]}); "
        f"generator late by up to {window.generator_late_s * 1e3:.2f} ms")
    log("[window] " + panel_pauses(proxy.panels, window.t0) + "; "
        + collections.summary(window.t0, window.t1))
    server.close()
    peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.local_devices()]
    memory_peak = max(peaks_in_use)
    log(f"[memory] peak bytes in use by device: {peaks_in_use}")
    tr = trace_mod.load(str(TRACE_DIR)) if trace else None

    # ----------------------------------------------------------- metrics
    run = Run(cell, peak, window, tr, setup_s)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    counts = window.counts()

    # ------------------------------------------------------- correctness
    done = window.served()
    rng = np.random.default_rng([seed, 0xC4])
    pick = sorted(rng.choice(len(done), size=min(SAMPLE, len(done)),
                             replace=False)) if done else []
    sample = [done[i] for i in pick]
    ids = np.stack([np.asarray(r.response.ids).reshape(-1) for r in sample])\
        if sample else np.zeros((0, top_n), np.int32)
    scores = np.stack([np.asarray(r.response.scores).reshape(-1)
                       for r in sample]) if sample else np.zeros((0, top_n))
    sample_queries = queries[[r.pool_index for r in sample]]
    del server, proxy, guard, pool, window, run, done, sample
    gc.collect()
    limits = cfg["checks"]
    checks = {"answers_checked": {"value": len(pick),
                                  "limit": limits["min_answers"]},
              "unanswered": {"value": counts["unanswered"], "limit": 0},
              "off_rung": {"value": counts["off_rung"], "limit": 0}}
    if len(pick):
        t_ref = time.monotonic()
        ref = Reference(catalog, params, cfg, tie_rel=limits["tie_rel"])
        got = compare(ref, sample_queries, ids, scores, top_n)
        log(f"[check] reference {time.monotonic() - t_ref:.2f} s")
        checks["score_gap"] = {"value": got["score_gap"],
                               "limit": limits["score_gap"]}
        checks["rank_gap"] = {"value": got["rank_gap"],
                              "limit": limits["rank_gap"]}
        log(f"[check] {len(pick)} answers; query codes at a k-th-latent "
            f"near-tie {got['query_ties']}, judged by the alternative "
            f"{got['alt_used']}")
    correct = (checks["answers_checked"]["value"]
               >= checks["answers_checked"]["limit"]
               and all(c["value"] <= c["limit"] for n, c in checks.items()
                       if n != "answers_checked")
               and "score_gap" in checks)
    result = {"correct": bool(correct), "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": metrics,
              "device": dict(device or {}, memory_peak_bytes=memory_peak)}
    if tr is not None:
        result["device"]["busy_s"] = tr.window_busy_s()
        result["device"]["window_s"] = tr.window_s()
        result["breakdown"] = tr.breakdown()
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    fault = layout_fault(cell)
    if fault:
        log(f"bench: {args.workload}: {fault}")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    device = require_chip(int(cell.spec["chips"]),
                          load_json(BENCH / "peaks.json"))
    import jax

    from repro.launch import use_compile_cache

    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"[device] {device}; compile cache {cache}")
    result = measure(cell, args.seed, args.seconds, bool(args.trace), device)
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
