"""The plain reference that decides ``correct``, and the comparison.

It imports nothing of the program and takes nothing the program made: it
regenerates the catalog rows from the seed, encodes them with a
straightforward float32 CompresSAE encoder (x/|x| @ W_enc + b_enc at
``Precision.HIGHEST``, the k largest |pre-activations| kept), stores them in
the configuration's index format (``f32``, or ``q8``: per-row symmetric
int8 with scale amax/127), and scores requests by cosine over the sparse
codes.

Rounding can legitimately change a code in two places, and nowhere else:

* the k-th and (k+1)-th largest |pre-activation| of a row lie within
  ``tie_rel`` of each other, so a differently rounded encoder may keep
  either;
* a ``q8`` value lies within ``tie_rel`` (relative to the value) of an
  int8 rounding boundary, so it may round to either neighbour.

For every catalog row the reference therefore keeps an interval
``[lo, hi]`` that holds the cosine of each code those choices allow
(the primary code's cosine, widened by the most the ambiguous entries can
move the dot product and the norm).  Rows without such an entry have
``lo == hi``.  A query's own code can only differ at the k-th boundary, so
the reference scores both of its codes when that boundary is a near-tie
and judges the answer by the one it fits better.

Two numbers compare one served answer (ids ``I``, scores ``v``) with it:

``score_gap``  max over served items of the distance of the served score
               from the item's interval (a wrong score, or a row served to
               the wrong request);
``rank_gap``   the best ``lo`` of any unserved row minus the worst ``hi``
               of a served one: how far the served set falls short of the
               reference's top-n (a missed or substituted item).  Negative
               when the served set is the top-n with room to spare.

``control_answers`` computes the reference's own answers with its values
rounded to a lower precision, which is what a later change that lowered
the precision would serve.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-8
SUB = 4096  # catalog rows per reference block


def _encode(w_enc, b_enc, x, k):
    """(vals (B, k+1), idx (B, k+1)): the k+1 largest |pre-activations|,
    largest first, with their signed values."""
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), EPS)
    pre = jnp.matmul(xn, w_enc, precision=HI) + b_enc
    _, idx = jax.lax.top_k(jnp.abs(pre), k + 1)
    return jnp.take_along_axis(pre, idx, axis=-1), idx


def _quantize(v, amax, levels):
    scale = jnp.maximum(amax / levels, 1e-12)
    q = jnp.clip(jnp.round(v / scale[:, None]), -levels, levels)
    return q * scale[:, None], v / scale[:, None], scale


@functools.partial(jax.jit, static_argnames=("k", "fmt", "tie_rel"))
def _catalog_codes(w_enc, b_enc, x, *, k, fmt, tie_rel):
    """One block of catalog rows -> the stored code, its raw values and its
    uncertainty entries (u_val (B, k+1) at latents u_idx)."""
    vals, idx = _encode(w_enc, b_enc, x, k)
    raw, cidx = vals[:, :k], idx[:, :k]
    tie = (jnp.abs(vals[:, k - 1]) - jnp.abs(vals[:, k])
           <= tie_rel * jnp.abs(vals[:, k - 1]))
    if fmt == "q8":
        amax = jnp.max(jnp.abs(raw), axis=-1)
        stored, ratio, scale = _quantize(raw, amax, 127.0)
        frac = ratio - jnp.floor(ratio)
        ambiguous = jnp.abs(frac - 0.5) <= tie_rel * jnp.abs(ratio)
        u_val = jnp.where(ambiguous, scale[:, None], 0.0)
        alt = jnp.clip(jnp.round(vals[:, k] / scale), -127, 127) * scale
    elif fmt == "f32":
        stored, u_val, alt = raw, jnp.zeros_like(raw), vals[:, k]
    else:
        raise ValueError(f"unknown index format {fmt!r}")
    # a near-tie at the k-th boundary may drop latent k-1 for latent k
    u_val = u_val.at[:, k - 1].add(jnp.where(tie, jnp.abs(stored[:, k - 1]),
                                             0.0))
    u_val = jnp.concatenate(
        [u_val, jnp.where(tie, jnp.abs(alt), 0.0)[:, None]], axis=1)
    # what the norm may gain or lose through those entries
    sq = jnp.sum(stored * stored, axis=-1)
    d_sq = jnp.sum(u_val[:, :k] * (2 * jnp.abs(stored) + u_val[:, :k]),
                   axis=-1) + u_val[:, k] ** 2
    return stored, raw, cidx, u_val, idx, sq, d_sq


def _dense(vals, idx, h):
    rows = jnp.arange(vals.shape[0])[:, None]
    return jnp.zeros((vals.shape[0], h), jnp.float32).at[rows, idx].add(vals)


def _bounds(dot, rad, sq, d_sq, qn):
    """Cosine interval from a dot product +- rad and a squared norm
    within sq +- d_sq (query norm qn)."""
    n_lo = jnp.sqrt(jnp.maximum(sq - d_sq, 0.0))
    n_hi = jnp.sqrt(sq + d_sq)
    d_lo, d_hi = dot - rad, dot + rad
    lo = jnp.where(d_lo >= 0, d_lo / jnp.maximum(n_hi, EPS),
                   d_lo / jnp.maximum(n_lo, EPS)) / qn
    hi = jnp.where(d_hi >= 0, d_hi / jnp.maximum(n_lo, EPS),
                   d_hi / jnp.maximum(n_hi, EPS)) / qn
    return lo, hi


def _round_values(v, control: str | None, raw=None):
    if control is None:
        return v
    if control == "bfloat16":
        return v.astype(jnp.bfloat16).astype(jnp.float32)
    if control == "int4":
        return _quantize(raw, jnp.max(jnp.abs(raw), axis=-1), 7.0)[0]
    raise ValueError(f"unknown control {control!r}")


class Reference:
    """The reference of one run.  It keeps nothing of the catalog: each pass
    regenerates the rows from the seed, encodes and stores them block by
    block (``SUB`` rows) and scores each block against the sample's query
    codes as it is made, so a device holds one block of reference codes
    whatever the catalog's size.  With ``shards`` > 1 in the configuration
    each shard's rows (whole chunks, in order) run on a device of their
    own, and the shards' tops are merged in shard order, which is the
    order of ids."""

    def __init__(self, catalog, params: dict, cfg: dict, *, tie_rel: float):
        self.k, self.h = int(cfg["k"]), int(cfg["h"])
        self.fmt = cfg["index_format"]
        self.n = catalog.n_rows
        self.tie_rel = float(tie_rel)
        self.catalog = catalog
        self.w_enc, self.b_enc = params["w_enc"], params["b_enc"]
        self.sub = min(SUB, catalog.chunk)
        shards = int(cfg.get("shards", 1))
        if catalog.n_chunks % shards:
            raise ValueError(f"{catalog.n_chunks} chunks do not split into "
                             f"{shards} shards")
        if shards > len(jax.local_devices()):
            raise ValueError(f"{shards} shards, {len(jax.local_devices())} "
                             f"devices")
        self.devices = ([None] if shards == 1
                        else jax.local_devices()[:shards])

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
        return self._pass(qd, qn, m, control, None)[0]

    def top_and_bounds(self, qd, qn, m: int, ids: np.ndarray):
        """``top(qd, qn, m)`` and, in the same pass, the (lo, hi), each
        (V, S, n), of the rows ``ids`` (S, n); ids outside [0, N) get
        -inf."""
        ids = np.asarray(ids)
        valid = (ids >= 0) & (ids < self.n)
        top, rows = self._pass(qd, qn, m, None, np.where(valid, ids, 0))
        lo, hi = _bounds_at(rows, qd, qn)
        lo, hi = np.array(lo, np.float64), np.array(hi, np.float64)
        lo[:, ~valid] = -np.inf
        hi[:, ~valid] = -np.inf
        return top, lo, hi

    def _pass(self, qd, qn, m, control, ids):
        """One pass over the catalog: ((values, ids) of the top ``m``, and
        with ``ids`` each served row's code entries, (S, n, ...) arrays).
        Chunk ``c`` runs on the device of shard ``c // (n_chunks /
        shards)``; at most two chunks a device are in flight."""
        cat, sub = self.catalog, self.sub
        per = cat.n_chunks // len(self.devices)
        on = [_Shard(d, (self.w_enc, self.b_enc, qd, qn, ids), m, self.k)
              for d in self.devices]
        for j in range(per):
            for s, sh in enumerate(on):
                c = s * per + j
                x = cat.chunk_rows(c, sh.device)
                for off in range(0, cat.chunk, sub):
                    valid = min(sub, cat.chunk - off)
                    xb = x[off:off + sub]
                    if valid < sub:   # the short last block, zero-padded
                        xb = jnp.pad(xb, ((0, sub - valid), (0, 0)))
                    blk = _catalog_codes(sh.w_enc, sh.b_enc, xb, k=self.k,
                                         fmt=self.fmt, tie_rel=self.tie_rel)
                    b0 = c * cat.chunk + off
                    sh.top = _top_step(sh.top, blk, b0, valid, sh.qd, sh.qn,
                                       m=m, control=control, h=self.h)
                    if ids is not None:
                        sh.rows = _gather_step(sh.rows, blk, b0, valid,
                                               sh.ids)
                del x
            if j:
                jax.block_until_ready([sh.done for sh in on])
            for sh in on:
                sh.done = sh.top
        if len(on) == 1:
            return on[0].top, on[0].rows
        # shard order is the order of ids: ties go to the lowest id, as in
        # one pass over every block in turn
        home = on[0].device
        vals, allid = (jnp.concatenate(
            [jax.device_put(sh.top[i], home) for sh in on], axis=-1)
            for i in (0, 1))
        top_v, pos = jax.lax.top_k(vals, m)
        top = (top_v, jnp.take_along_axis(allid, pos, axis=-1))
        if ids is None:
            return top, None
        owner = jnp.asarray(ids // (self.n // len(on)), jnp.int32)
        rows = on[0].rows
        for s, sh in enumerate(on[1:], 1):
            mine = owner == s
            rows = tuple(
                jnp.where(mine.reshape(mine.shape + (1,) * (a.ndim - 2)),
                          jax.device_put(b, home), a)
                for a, b in zip(rows, sh.rows))
        return top, rows


class _Shard:
    """One device's part of a pass: its copies of the weights, the query
    codes and the served ids, its running top ``m`` and the code entries of
    the served rows it has found."""

    def __init__(self, device, arrays, m: int, k: int):
        w_enc, b_enc, qd, qn, ids = arrays
        v_, s_, _ = qd.shape
        top = (jnp.full((v_, s_, m), -jnp.inf), jnp.full((v_, s_, m), -1))
        rows = None
        if ids is not None:
            ids = jnp.asarray(ids, jnp.int32)
            f32, i32 = jnp.float32, jnp.int32
            rows = tuple(jnp.zeros(ids.shape + tail, t) for tail, t in (
                ((k,), f32), ((k,), i32), ((k + 1,), f32), ((k + 1,), i32),
                ((), f32), ((), f32)))
        arrays = (w_enc, b_enc, qd, qn, ids, top, rows)
        if device is not None:
            arrays = jax.device_put(arrays, device)
        (self.w_enc, self.b_enc, self.qd, self.qn, self.ids, self.top,
         self.rows) = arrays
        self.device = device
        self.done = None


@functools.partial(jax.jit, static_argnames=("m", "control", "h"))
def _top_step(carry, blk, b0, valid, qd, qn, *, m, control, h):
    """The running top ``m`` of each (variant, query) after one block of
    ``valid`` rows starting at row ``b0`` (rows past ``valid`` are padding
    and never enter)."""
    v_, s_, _ = qd.shape
    qflat = qd.reshape(v_ * s_, h)
    qnf = qn.reshape(v_ * s_, 1)
    if control == "bfloat16":
        qflat = _round_values(qflat, control)
        qnf = jnp.maximum(jnp.sqrt(jnp.sum(qflat * qflat, axis=-1,
                                           keepdims=True)), EPS)
    carry = (carry[0].reshape(v_ * s_, m), carry[1].reshape(v_ * s_, m))
    stored, raw, cidx, u_val, u_idx, sq, d_sq = blk
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
    real = jnp.arange(score.shape[1]) < valid
    score = jnp.where(real, score, -jnp.inf)
    ids = jnp.where(real, b0 + jnp.arange(score.shape[1]), -1)
    vals = jnp.concatenate([carry[0], score], axis=1)
    allid = jnp.concatenate(
        [carry[1], jnp.broadcast_to(ids, score.shape)], axis=1)
    top_v, pos = jax.lax.top_k(vals, m)
    top_i = jnp.take_along_axis(allid, pos, axis=1)
    return top_v.reshape(v_, s_, m), top_i.reshape(v_, s_, m)


@jax.jit
def _gather_step(rows, blk, b0, valid, ids):
    """``rows`` (each (S, n, ...)) with the code entries of the served ids
    that lie in the block's ``valid`` rows from row ``b0`` filled in."""
    local = ids - b0
    here = (local >= 0) & (local < valid)
    at = jnp.where(here, local, 0)
    stored, _, cidx, u_val, u_idx, sq, d_sq = blk
    found = tuple(a[at] for a in (stored, cidx, u_val, u_idx, sq, d_sq))
    return tuple(jnp.where(here.reshape(here.shape + (1,) * (a.ndim - 2)),
                           a, r) for a, r in zip(found, rows))


@jax.jit
def _bounds_at(rows, qd, qn):
    stored, cidx, u_val, u_idx, sq, d_sq = rows
    # each query's dense code read at its served rows' latents
    at = jax.vmap(lambda q, i: q[i])                   # over requests
    q_at = jax.vmap(at, (0, None))(qd, cidx)          # (V, S, n, k)
    u_at = jax.vmap(at, (0, None))(jnp.abs(qd), u_idx)
    dot = jnp.sum(q_at * stored[None], axis=-1)
    rad = jnp.sum(u_at * u_val[None], axis=-1)
    return _bounds(dot, rad, sq[None], d_sq[None], qn[:, :, None])


def compare(ref: Reference, queries: np.ndarray, ids: np.ndarray,
            scores: np.ndarray, top_n: int) -> dict:
    """Per-request and worst ``score_gap`` / ``rank_gap`` of served answers
    (ids, scores: (S, n)) to the requests' dense queries (S, d)."""
    qd, qn, qtie = ref.query_codes(queries)
    (top_v, top_i), lo, hi = ref.top_and_bounds(qd, qn, top_n + 1, ids)
    top_v, top_i = np.asarray(top_v), np.asarray(top_i)   # lo, hi (V, S, n)
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


def control_answers(ref: Reference, queries: np.ndarray, top_n: int,
                    control: str) -> tuple[np.ndarray, np.ndarray]:
    """The answers the reference gives with its code values rounded to
    ``control`` precision ("bfloat16", or "int4" storage for q8): (scores,
    ids), each (S, n), from each query's primary code."""
    qd, qn, _ = ref.query_codes(queries)
    v, i = ref.top(qd, qn, top_n, control=control)
    return np.asarray(v[0]), np.asarray(i[0])
