"""The harness end to end at a tiny size on the CPU (kernels interpreted),
the faults a serving cell can have, and the refusals of the measurement
path.

``measure`` is the whole run after the look for a chip: set-up, warm-up,
the window, the metric readers and the check against the reference."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
import run as harness  # noqa: E402


def tiny_cell(name="compresae-1m-q8.full", clients=16):
    cell = harness.Cell(name)
    cell.config.update(d=64, h=256, k=8, n_rows=2048, encode_chunk=1024)
    cell.config["training"].update(steps=3, batch=256)
    cell.traffic.update(clients=clients, warm_panels=[clients],
                        query_pool=64, users=50)
    return cell


def measure(cell, seed=7, trace=False, seconds=2.0):
    return harness.measure(cell, seed, seconds, trace, None, use_kernel=True)


def _result_line(stdout: str):
    for line in reversed(stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


@pytest.mark.parametrize("name,clients,metrics", [
    ("compresae-1m-q8.full", 16, {"qps", "p50_ms", "p95_ms", "setup_s"}),
    ("compresae-1m-f32.full", 16, {"qps", "p50_ms", "p95_ms", "setup_s"}),
    ("compresae-1m-q8.lone", 1, {"p50_ms", "p95_ms", "setup_s"}),
])
def test_tiny_cell_is_correct_and_reports_its_metrics(name, clients,
                                                      metrics):
    res = measure(tiny_cell(name, clients), seed=2 ** 32 + 9)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == metrics
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["checks"]["answers_checked"]["value"] > 8
    assert list(res)[-1] == "checks"


def test_traced_run_leaves_out_what_it_cannot_read():
    """On the CPU the trace has no TPU ops: the device metrics are left
    out, never reported as 0; the batcher's own stamp is still read."""
    res = measure(tiny_cell("compresae-1m-q8.lone", clients=1), trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"queue_wait_ms"}
    assert res["device"]["window_s"] == pytest.approx(2.0, rel=0.05)


def _corrupt(monkeypatch, how):
    """Break the engine's answer where it is produced, for the panels of
    the window (16 rows) only, so set-up's self-check still passes."""
    from repro.serving import engine as engine_mod

    orig = engine_mod.RetrievalEngine.retrieve_dense

    def broken(self, x, n):
        resp = orig(self, x, n)
        if x.shape[0] < 16 or self.use_fused is False:
            return resp
        s, i = resp.scores, resp.ids
        if how == "id":
            i = i.at[:, 0].set((i[:, 0] + 1) % self.index.codes.n)
        elif how == "score":
            s = s.at[:, 5].add(-1e-3)
        elif how == "rows":
            s, i = s[::-1], i[::-1]
        elif how == "half":
            # half of the panel left out: its rows get the other half's
            h = x.shape[0] // 2
            s = s.at[h:].set(s[:h])
            i = i.at[h:].set(i[:h])
        return resp._replace(scores=s, ids=i)

    monkeypatch.setattr(engine_mod.RetrievalEngine, "retrieve_dense",
                        broken)


@pytest.mark.parametrize("how", ["id", "score", "rows", "half"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, how):
    _corrupt(monkeypatch, how)
    res = measure(tiny_cell())
    assert not res["correct"]
    gaps = res["checks"]
    assert max(gaps["score_gap"]["value"] / gaps["score_gap"]["limit"],
               gaps["rank_gap"]["value"] / gaps["rank_gap"]["limit"]) > 1


def test_an_answer_off_the_configured_rung_is_not_correct(monkeypatch):
    from repro.serving import guard as guard_mod

    orig = guard_mod.GuardedEngine.retrieve_dense

    def off_rung(self, x, n, **kw):
        resp = orig(self, x, n, **kw)
        return resp._replace(status=resp.status._replace(
            path="fp32-ref", step=3, degraded=True))

    monkeypatch.setattr(guard_mod.GuardedEngine, "retrieve_dense", off_rung)
    res = measure(tiny_cell())
    assert not res["correct"]
    assert res["checks"]["off_rung"]["value"] == res["attempted"] > 0
    assert res["failed"] == res["attempted"]


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "compresae-1m-q8.lone", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert _result_line(p.stdout) is None
    assert "TPU" in p.stderr


def test_run_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "compresae-1m-q8.lone", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert _result_line(p.stdout) is None


def test_control_in_lower_precision_fails_the_limits():
    """The control: the reference put in the program's place in the
    precision below the configuration's (bfloat16 values; int4 storage
    for q8).  Each configuration's limits must refuse it."""
    from yardstick.catalog import Catalog, ZipfStream, train_weights
    from yardstick.reference import Reference, compare, control_answers

    for name, controls in (("compresae-1m-q8.full", ("bfloat16", "int4")),
                           ("compresae-1m-f32.full", ("bfloat16",))):
        cell = tiny_cell(name)
        cfg, lim = cell.config, cell.config["checks"]
        cat = Catalog(11, cfg)
        params = train_weights(cat, 11, cfg)
        _, q = ZipfStream(cat.users(11, 50), zipf_a=1.1, jitter=0.05,
                          seed=11).sample(64)
        ref = Reference(cat, params, cfg, tie_rel=lim["tie_rel"])
        exact_v, exact_i = control_answers(ref, q, 100, None)
        ok = compare(ref, q, exact_i, exact_v, 100)
        assert ok["score_gap"] <= lim["score_gap"]
        assert ok["rank_gap"] <= lim["rank_gap"]
        for control in controls:
            v, i = control_answers(ref, q, 100, control)
            got = compare(ref, q, i, v, 100)
            assert (got["score_gap"] > lim["score_gap"]
                    or got["rank_gap"] > lim["rank_gap"]), (name, control)
