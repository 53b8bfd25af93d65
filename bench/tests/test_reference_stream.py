"""The streamed reference against the stored one it replaced, which this
file keeps as the oracle (``StoredReference``, ``stored_compare``: the
code as it was, every catalog row's reference code kept on one device).
Tops, bounds and ``compare`` output must be bit-identical, on one device
and, in a subprocess with four CPU devices, on four shards, with ties
across every chunk and shard boundary; a short last block is padded and
its padding never enters a top or a bound."""
import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    for p in (BENCH, BENCH.parent / "src", BENCH.parent):
        sys.path.insert(0, str(p))

from yardstick import reference  # noqa: E402
from yardstick.catalog import Catalog, ZipfStream, train_weights  # noqa: E402
from yardstick.reference import (  # noqa: E402
    EPS, HI, Reference, _bounds, _catalog_codes, _dense, _encode,
    _gather_step, _round_values, _top_step, compare,
)


# ------------------------------------------------ the oracle, as it was
class StoredReference:
    """The reference index of one run: every catalog row's code, encoded
    and stored by the reference, kept on the device in blocks."""

    def __init__(self, catalog, params: dict, cfg: dict, *, tie_rel: float,
                 sub: int):
        self.k, self.h = int(cfg["k"]), int(cfg["h"])
        self.fmt = cfg["index_format"]
        self.n = catalog.n_rows
        self.tie_rel = float(tie_rel)
        self.w_enc, self.b_enc = params["w_enc"], params["b_enc"]
        blocks = []
        for c in range(catalog.n_chunks):
            x = catalog.chunk_rows(c)
            for s in range(0, catalog.chunk, sub):
                blocks.append(_catalog_codes(
                    self.w_enc, self.b_enc, x[s:s + sub], k=self.k,
                    fmt=self.fmt, tie_rel=self.tie_rel))
            del x
        # (blocks, sub, ...) stacks: one reference step per block
        self.codes = [jnp.stack(part) for part in zip(*blocks)]
        self.sub = sub

    # ------------------------------------------------------------ queries
    def query_codes(self, x: np.ndarray):
        """Dense (S, d) queries -> (qd (2, S, h), qn (2, S), tie (S,)): the
        primary code and the code with its k-th latent replaced by the
        (k+1)-th, as dense vectors, with their norms."""
        k = self.k
        vals, idx = jax.jit(_encode, static_argnames="k")(
            self.w_enc, self.b_enc, jnp.asarray(x, jnp.float32), k=k)
        tie = (jnp.abs(vals[:, k - 1]) - jnp.abs(vals[:, k])
               <= self.tie_rel * jnp.abs(vals[:, k - 1]))
        alt_v = vals[:, :k].at[:, k - 1].set(vals[:, k])
        alt_i = idx[:, :k].at[:, k - 1].set(idx[:, k])
        qd = jnp.stack([_dense(vals[:, :k], idx[:, :k], self.h),
                        _dense(alt_v, alt_i, self.h)])
        qn = jnp.maximum(jnp.sqrt(jnp.sum(qd * qd, axis=-1)), EPS)
        return qd, qn, np.asarray(tie)

    # ---------------------------------------------------------- scanning
    def top(self, qd, qn, m: int, control: str | None = None):
        """Each query's ``m`` best rows over the whole catalog: by ``lo``
        (the reference), or, with ``control``, by the cosine of its own
        codes rounded to that precision.  Returns (values, ids), each
        (V, S, m)."""
        return _stored_scan(tuple(self.codes), qd, qn, m=m, control=control,
                     h=self.h, sub=self.sub)

    def bounds(self, qd, qn, ids: np.ndarray):
        """(lo, hi), each (V, S, n), of the rows ``ids`` (S, n); ids outside
        [0, N) get -inf."""
        ids = np.asarray(ids)
        valid = (ids >= 0) & (ids < self.n)
        lo, hi = _stored_bounds_at(tuple(self.codes), qd, qn,
                            jnp.asarray(np.where(valid, ids, 0)),
                            sub=self.sub)
        lo, hi = np.array(lo, np.float64), np.array(hi, np.float64)
        lo[:, ~valid] = -np.inf
        hi[:, ~valid] = -np.inf
        return lo, hi


@functools.partial(jax.jit, static_argnames=("m", "control", "h", "sub"))
def _stored_scan(codes, qd, qn, *, m, control, h, sub):
    v_, s_, _ = qd.shape
    qflat = qd.reshape(v_ * s_, h)
    qnf = qn.reshape(v_ * s_, 1)
    if control == "bfloat16":
        qflat = _round_values(qflat, control)
        qnf = jnp.maximum(jnp.sqrt(jnp.sum(qflat * qflat, axis=-1,
                                           keepdims=True)), EPS)
    init = (jnp.full((v_ * s_, m), -jnp.inf), jnp.full((v_ * s_, m), -1))

    def step(carry, blk):
        b, (stored, raw, cidx, u_val, u_idx, sq, d_sq) = blk
        if control is None:
            dot = jnp.matmul(qflat, _dense(stored, cidx, h).T, precision=HI)
            rad = jnp.matmul(jnp.abs(qflat), _dense(u_val, u_idx, h).T,
                             precision=HI)
            score, _ = _bounds(dot, rad, sq[None], d_sq[None], qnf)
        else:
            cv = _round_values(stored, control, raw)
            cn = jnp.maximum(jnp.sqrt(jnp.sum(cv * cv, axis=-1)), EPS)
            score = jnp.matmul(qflat, _dense(cv, cidx, h).T,
                               precision=HI) / (cn[None] * qnf)
        ids = b * sub + jnp.arange(score.shape[1])
        vals = jnp.concatenate([carry[0], score], axis=1)
        allid = jnp.concatenate(
            [carry[1], jnp.broadcast_to(ids, score.shape)], axis=1)
        top_v, pos = jax.lax.top_k(vals, m)
        return (top_v, jnp.take_along_axis(allid, pos, axis=1)), None

    nblk = codes[0].shape[0]
    (top_v, top_i), _ = jax.lax.scan(step, init, (jnp.arange(nblk), codes))
    return top_v.reshape(v_, s_, m), top_i.reshape(v_, s_, m)


@functools.partial(jax.jit, static_argnames=("sub",))
def _stored_bounds_at(codes, qd, qn, ids, *, sub):
    blk, row = ids // sub, ids % sub
    stored, _, cidx, u_val, u_idx, sq, d_sq = (a[blk, row] for a in codes)
    # each query's dense code read at its served rows' latents
    at = jax.vmap(lambda q, i: q[i])                   # over requests
    q_at = jax.vmap(at, (0, None))(qd, cidx)          # (V, S, n, k)
    u_at = jax.vmap(at, (0, None))(jnp.abs(qd), u_idx)
    dot = jnp.sum(q_at * stored[None], axis=-1)
    rad = jnp.sum(u_at * u_val[None], axis=-1)
    return _bounds(dot, rad, sq[None], d_sq[None], qn[:, :, None])


def stored_compare(ref: StoredReference, queries: np.ndarray, ids: np.ndarray,
            scores: np.ndarray, top_n: int) -> dict:
    """Per-request and worst ``score_gap`` / ``rank_gap`` of served answers
    (ids, scores: (S, n)) to the requests' dense queries (S, d)."""
    qd, qn, qtie = ref.query_codes(queries)
    lo, hi = ref.bounds(qd, qn, ids)                   # (V, S, n)
    top_v, top_i = (np.asarray(a) for a in ref.top(qd, qn, top_n + 1))
    scores = np.asarray(scores, np.float64)
    below = np.maximum(lo - scores[None], 0.0)
    above = np.maximum(scores[None] - hi, 0.0)
    sgap = np.max(np.where(np.isfinite(lo), below + above, np.inf), axis=-1)
    served = (top_i[..., :, None] == ids[None, :, None, :]).any(-1)
    unserved_best = np.max(np.where(served, -np.inf, top_v), axis=-1)
    rgap = unserved_best - np.min(hi, axis=-1)        # (V, S)
    worst = np.maximum(sgap, rgap)
    use_alt = qtie & (worst[1] < worst[0])
    pick = lambda a: np.where(use_alt, a[1], a[0])  # noqa: E731
    sg, rg = pick(sgap), pick(rgap)
    return {"score_gap": float(np.max(sg)), "rank_gap": float(np.max(rg)),
            "per_request": {"score_gap": sg, "rank_gap": rg},
            "query_ties": int(qtie.sum()), "alt_used": int(use_alt.sum())}


# ------------------------------------------------------------ the data
def tiny_cfg(*, shards=1, n_rows=4096, chunk=512, fmt="q8"):
    name = "compresae-1m-q8" if fmt == "q8" else "compresae-1m-f32"
    cfg = dict(reference_cfg(name))
    cfg.update(d=64, h=256, k=8, n_rows=n_rows, encode_chunk=chunk)
    cfg["training"] = dict(cfg["training"], steps=3, batch=256)
    if shards > 1:
        cfg["shards"] = shards
    return cfg


def reference_cfg(name):
    import json

    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


class RepeatCatalog(Catalog):
    """Every chunk holds chunk 0's rows: each row has a twin in every
    other chunk, so every top is decided by ties across chunk and shard
    boundaries."""

    def chunk_rows(self, c, device=None):
        return super().chunk_rows(0, device)


def _answers(oracle, q, top_n, seed):
    """Served answers to judge: the oracle's own top-n, then altered as a
    faulty program would (a row swapped for a far one, a score nudged, ids
    outside [0, N))."""
    qd, qn, _ = oracle.query_codes(q)
    v, i = (np.array(a[0]) for a in oracle.top(qd, qn, top_n))
    rng = np.random.default_rng(seed)
    rows = rng.choice(len(q), size=6, replace=False)
    i[rows[0], -1] = (i[rows[0], -1] + oracle.n // 2) % oracle.n
    v[rows[1], 3] -= 1e-3
    i[rows[2], 0] = -1
    i[rows[3], 5] = oracle.n
    i[rows[4]] = i[rows[4]][::-1]
    return v, i


def check_against_oracle(cfg, *, sub, oracle_sub=None, catalog=Catalog,
                         top_n=100, exact=True):
    """Streamed vs stored on one seeded catalog: tops, bounds and compare
    output (bit for bit where ``exact``), and no padding row anywhere."""
    cat = catalog(11, cfg)
    params = train_weights(cat, 11, cfg)
    _, q = ZipfStream(cat.users(11, 50), zipf_a=1.1, jitter=0.05,
                      seed=11).sample(48)
    tie_rel = cfg["checks"]["tie_rel"]
    oracle = StoredReference(cat, params, cfg, tie_rel=tie_rel,
                             sub=oracle_sub or sub)
    old = reference.SUB
    reference.SUB = sub
    try:
        ref = Reference(cat, params, cfg, tie_rel=tie_rel)
    finally:
        reference.SUB = old
    assert ref.sub == sub
    v, i = _answers(oracle, q, top_n, 5)
    qd, qn, _ = ref.query_codes(q)
    (tv, ti), lo, hi = ref.top_and_bounds(qd, qn, top_n + 1, i)
    ov, oi = oracle.top(qd, qn, top_n + 1)
    olo, ohi = oracle.bounds(qd, qn, i)
    tv, ti, ov, oi = (np.asarray(a) for a in (tv, ti, ov, oi))
    assert ((ti >= 0) & (ti < cat.n_rows)).all()
    got, want = compare(ref, q, i, v, top_n), stored_compare(oracle, q, i, v,
                                                             top_n)
    for c in ("bfloat16",) + (("int4",) if cfg["index_format"] == "q8"
                               else ()):
        cv, ci = ref.top(qd, qn, top_n, control=c)
        wv, wi = oracle.top(qd, qn, top_n, control=c)
        assert np.array_equal(np.asarray(ci), np.asarray(wi)), c
        assert np.array_equal(np.asarray(cv), np.asarray(wv)), c
    if exact:
        assert np.array_equal(ti, oi) and np.array_equal(tv, ov)
        assert np.array_equal(lo, olo) and np.array_equal(hi, ohi)
        for key in ("score_gap", "rank_gap"):
            assert np.array_equal(got["per_request"][key],
                                  want["per_request"][key]), key
            assert got[key] == want[key]
        assert (got["query_ties"], got["alt_used"]) == (
            want["query_ties"], want["alt_used"])
    else:
        np.testing.assert_allclose(tv, ov, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(lo, olo, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(hi, ohi, rtol=1e-6, atol=1e-7)
    return got


# ----------------------------------------------------------- the tests
@pytest.mark.parametrize("fmt", ["q8", "f32"])
@pytest.mark.parametrize("catalog", [Catalog, RepeatCatalog])
def test_streamed_equals_stored_on_one_device(fmt, catalog):
    got = check_against_oracle(tiny_cfg(fmt=fmt), sub=128, catalog=catalog)
    # the altered answers are caught, the rest of the sample is not
    assert got["score_gap"] > 1e-5 or got["rank_gap"] > 1e-5


def test_short_last_block_is_padded_and_never_enters():
    """640-row chunks in 256-row blocks: the third block of each chunk has
    128 rows and 128 of padding."""
    check_against_oracle(tiny_cfg(n_rows=5120, chunk=640), sub=256,
                         oracle_sub=128, exact=False)


def test_a_blocks_padding_rows_never_enter_a_top_or_a_bound():
    """A block of 64 real rows and 64 rows of padding that are the queries
    themselves, so that unmasked they would lead every top."""
    cfg = tiny_cfg()
    cat = Catalog(11, cfg)
    params = train_weights(cat, 11, cfg)
    ref = Reference(cat, params, cfg, tie_rel=cfg["checks"]["tie_rel"])
    q = np.asarray(cat.chunk_rows(1)[:4])
    qd, qn, _ = ref.query_codes(q)
    x = jnp.concatenate([cat.chunk_rows(0)[:64], jnp.asarray(q).repeat(16, 0)])
    blk = _catalog_codes(ref.w_enc, ref.b_enc, x, k=ref.k, fmt=ref.fmt,
                         tie_rel=ref.tie_rel)
    m, b0 = 96, 1000
    init = (jnp.full((2, 4, m), -jnp.inf), jnp.full((2, 4, m), -1))
    for control in (None, "bfloat16"):
        v, i = (np.asarray(a) for a in _top_step(
            init, blk, b0, 64, qd, qn, m=m, control=control, h=ref.h))
        real = np.isfinite(v)
        assert (real.sum(-1) == 64).all()
        assert ((i[real] >= b0) & (i[real] < b0 + 64)).all()
        assert (i[~real] == -1).all()
    # served ids at the block's rows 63, 64 (padding) and 127 (padding)
    ids = jnp.asarray([[b0 + 63, b0 + 64, b0 + 127]] * 4, jnp.int32)
    rows = tuple(jnp.full(ids.shape + a.shape[1:], 7, a.dtype)
                 for a in (blk[0], blk[2], blk[3], blk[4], blk[5], blk[6]))
    got = _gather_step(rows, blk, b0, 64, ids)
    for g, a in zip(got, (blk[0], blk[2], blk[3], blk[4], blk[5], blk[6])):
        g = np.asarray(g)
        assert np.array_equal(g[:, 0], np.broadcast_to(np.asarray(a[63]),
                                                       g[:, 0].shape))
        assert (g[:, 1:] == 7).all()


def test_streamed_on_four_shards_equals_stored_on_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    assert p.stdout.strip().splitlines()[-1] == "ok four devices"


def _four_devices():
    assert len(jax.local_devices()) == 4
    for fmt in ("q8", "f32"):
        for catalog in (Catalog, RepeatCatalog):
            check_against_oracle(tiny_cfg(shards=4, fmt=fmt), sub=128,
                                 catalog=catalog)
    check_against_oracle(tiny_cfg(shards=4, n_rows=5120, chunk=640),
                         sub=256, oracle_sub=128, exact=False)
    print("ok four devices")


if __name__ == "__main__":
    _four_devices()
