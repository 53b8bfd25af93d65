#!/usr/bin/env python3
"""The control of each cell's comparison, run on the chip at the cell's
own size, for several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

It makes what a run makes (catalog, encoder weights, the program's index
and engine, the reference) once, and for each seed the query pool, from
which it draws a sample of requests the way a run does.  It then reads the
two numbers of ``bench/yardstick/reference.py`` for

* ``program``: the program's own exact path (the guarded engine's
  ``retrieve_dense`` on panels of 64), a reading of the lower end;
* ``bfloat16``: the reference with its code values in bfloat16, the step
  below the configuration's float32 scoring;
* ``int4`` (``q8`` only): the reference with int4 storage, the step below
  the configuration's int8 values;
* ``program-int8`` (``q8`` only): the program's own int8-scoring path
  (``precision="int8"``) over the same index.

Each control has to fail a limit of ``bench/configs/<config>.json``; the
limits are set between the program's readings and the controls'.  The
benchmark's own runs never run this.
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
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import run as harness

    cell = harness.Cell(args.workload)
    harness.require_chip(int(cell.spec["chips"]),
                         harness.load_json(BENCH / "peaks.json"))
    import jax
    import numpy as np

    from repro.launch import use_compile_cache
    from repro.serving import EngineConfig, RetrievalEngine
    from yardstick.catalog import Catalog, ZipfStream, train_weights
    from yardstick.reference import Reference, compare, control_answers
    from yardstick.system import build_engine

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cfg, traffic = cell.config, cell.traffic
    lim, top_n = cfg["checks"], int(traffic["top_n"])
    controls = ["bfloat16"] + (["int4"] if cfg["index_format"] == "q8"
                               else [])
    data_seed = int(cfg["data_seed"])
    catalog = Catalog(data_seed, cfg)
    params = train_weights(catalog, data_seed, cfg)
    guard = build_engine(cfg, catalog, params)
    engines = {"program": guard}
    if cfg["index_format"] == "q8":
        engines["program-int8"] = RetrievalEngine(
            guard.engine.index, params,
            config=EngineConfig(use_kernel="auto", precision="int8"))
    users = catalog.users(data_seed, int(traffic["users"]))
    ref = Reference(catalog, params, cfg, tie_rel=lim["tie_rel"])
    for seed in (int(s) for s in args.seeds.split(",")):
        stream = ZipfStream(users, zipf_a=float(traffic["zipf_a"]),
                            jitter=float(traffic["jitter"]), seed=seed)
        _, pool = stream.sample(int(traffic["query_pool"]))
        rng = np.random.default_rng([seed, 0xC4])
        q = pool[np.sort(rng.choice(len(pool), harness.SAMPLE,
                                    replace=False))]
        served = {name: _serve(e, q, top_n) for name, e in engines.items()}
        for c in controls:
            served[c] = control_answers(ref, q, top_n, c)
        for name, (v, i) in served.items():
            got = compare(ref, q, np.asarray(i), np.asarray(v), top_n)
            fails = (got["score_gap"] > lim["score_gap"]
                     or got["rank_gap"] > lim["rank_gap"])
            print(json.dumps({
                "workload": args.workload, "seed": seed, "answers": name,
                "score_gap": got["score_gap"], "rank_gap": got["rank_gap"],
                "fails_limits": fails, "query_ties": got["query_ties"]}),
                flush=True)
    return 0


def _serve(engine, q, top_n, panel=64):
    """Answers of ``engine.retrieve_dense`` to queries ``q`` in panels."""
    import jax.numpy as jnp
    import numpy as np

    vs, ids = [], []
    for s in range(0, len(q), panel):
        resp = engine.retrieve_dense(jnp.asarray(q[s:s + panel]), top_n)
        vs.append(np.asarray(resp.scores))
        ids.append(np.asarray(resp.ids))
    return np.concatenate(vs), np.concatenate(ids)


if __name__ == "__main__":
    sys.exit(main())
