#!/usr/bin/env python3
"""Sweep of open-loop arrival rates over one configuration, on the chip,
to find the highest rate the serving stack sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 40,60,80,100

It sets the cell up once and then, for each rate, runs the ``open`` driver
for ``--seconds`` and prints one JSON line: offered and answered rate, p50
and p95 latency, the latency of the first and last fifth of the window's
requests (a backlog that grows shows as a last fifth far slower than the
first), and shed requests.  A fixed-rate open-loop cell takes about four
fifths of the highest rate at which the backlog does not grow.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import run as harness

    cell = harness.Cell(args.workload)
    harness.require_chip(int(cell.spec["chips"]),
                         harness.load_json(BENCH / "peaks.json"))
    import jax
    import numpy as np

    from repro.errors import QueueFullError
    from repro.launch import use_compile_cache
    from repro.serving import MicrobatchServer
    from yardstick.catalog import Catalog, ZipfStream, train_weights
    from yardstick.system import PanelSpan, build_engine, query_pool, warm_up
    from yardstick.window import Window, percentile

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg, traffic = cell.config, dict(cell.traffic)
    top_n = int(traffic["top_n"])
    data_seed = int(cfg["data_seed"])
    catalog = Catalog(data_seed, cfg)
    params = train_weights(catalog, data_seed, cfg)
    guard = build_engine(cfg, catalog, params)
    stream = ZipfStream(catalog.users(data_seed, int(traffic["users"])),
                        zipf_a=float(traffic["zipf_a"]),
                        jitter=float(traffic["jitter"]), seed=args.seed)
    pool = query_pool(stream.sample(int(traffic["query_pool"]))[1])
    proxy = PanelSpan(guard)
    driver = harness.load_module(BENCH / "drivers" / "open.py", "open")
    with MicrobatchServer(proxy) as server:
        warm_up(server, pool, top_n, list(range(1, 65)), harness.DRAIN_S)
        for rate in (float(r) for r in args.rates.split(",")):
            proxy.panels.clear()
            window = Window(args.seconds, cfg["path"])
            traffic["rate_rps"] = rate
            traffic["schedule_seed"] = args.seed
            driver.run(harness.Context(server, pool, traffic, args.seed,
                                       QueueFullError), window)
            lat = window.latencies_s()
            fifth = max(1, len(lat) // 5)
            rows = [r for _, _, r in proxy.panels]
            print(json.dumps({
                "rate_rps": rate,
                "answered_rps": window.served_rate(),
                "p50_ms": percentile(lat, 50) * 1e3,
                "p95_ms": percentile(lat, 95) * 1e3,
                "first_fifth_ms": float(np.mean(lat[:fifth])) * 1e3,
                "last_fifth_ms": float(np.mean(lat[-fifth:])) * 1e3,
                "panels_by_rows": {str(b): rows.count(b)
                                   for b in sorted(set(rows))},
                **window.counts()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
