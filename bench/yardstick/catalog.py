"""What the benchmark makes from ``--seed``: the catalog, the users and their
query stream, and the encoder weights.

Nothing here imports the system under test.  The program receives the
catalog rows, the weights and the queries, and makes everything else (codes,
index, engine) itself; the reference in ``reference.py`` regenerates the
same rows from the same seed.

* ``catalog_chunk`` — a clustered, spectrally decaying catalog, one chunk of
  rows at a time, so the dense catalog never exists whole.  Its centroids
  depend on the seed only, the rows of chunk ``c`` on ``(seed, c)``.  The
  construction follows ``repro.data.synthetic.clustered_embeddings``
  (heavy-tailed cluster sizes, decaying spectrum, per-row scale jitter).
* ``ZipfStream`` — a copy of ``repro.data.sampler.ZipfianQueryStream``:
  bounded-Zipf user ranks, each request its user's vector plus Gaussian
  jitter.  Host-side numpy, seeded.
* ``train_weights`` — CompresSAE weights (paper eq. 1-3): W_dec rows unit
  norm, W_enc tied to W_dec at init, ``steps`` Adam steps on the multi-k
  cosine loss over fresh catalog rows, all in one jitted call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that uses every bit of ``seed`` (``PRNGKey`` keeps the
    low 32 only, so 7 and 2**32 + 7 would collide)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _stream(seed: int, name: str) -> jax.Array:
    """An independent key per named stream (catalog, users, weights...)."""
    tag = sum(ord(c) << (8 * (i % 3)) for i, c in enumerate(name))
    return jax.random.fold_in(seed_key(seed), tag)


@functools.partial(jax.jit, static_argnames=("d", "n_clusters", "decay"))
def _centroids(key, *, d: int, n_clusters: int, decay: float):
    spectrum = decay ** (jnp.arange(d) / (d / 8.0))
    return jax.random.normal(key, (n_clusters, d)) * spectrum, spectrum


@functools.partial(jax.jit, static_argnames=("rows", "noise", "zipf_a"))
def _rows(key, centroids, spectrum, *, rows: int, noise: float,
          zipf_a: float):
    n_clusters, d = centroids.shape
    k_assign, k_noise, k_scale = jax.random.split(key, 3)
    u = jax.random.uniform(k_assign, (rows,), minval=1e-6, maxval=1.0)
    assign = jnp.clip(u ** (-1.0 / zipf_a) - 1.0, 0,
                      n_clusters - 1).astype(jnp.int32)
    x = centroids[assign] + noise * jax.random.normal(
        k_noise, (rows, d)) * spectrum
    return x * jnp.exp(0.1 * jax.random.normal(k_scale, (rows, 1)))


class Catalog:
    """The seeded catalog of one configuration, made chunk by chunk."""

    def __init__(self, seed: int, cfg: dict):
        spec = cfg["catalog"]
        self.n_rows = int(cfg["n_rows"])
        self.chunk = int(cfg["encode_chunk"])
        if self.n_rows % self.chunk:
            raise ValueError(f"n_rows {self.n_rows} is not a multiple of "
                             f"encode_chunk {self.chunk}")
        self._key = _stream(seed, "catalog")
        self.noise = float(spec["noise"])
        self.zipf_a = float(spec["zipf_a"])
        self.shape_arrays = _centroids(
            jax.random.fold_in(self._key, 0), d=int(cfg["d"]),
            n_clusters=int(spec["n_clusters"]),
            decay=float(spec["spectrum_decay"]))
        self._on_device: dict = {}

    @property
    def n_chunks(self) -> int:
        return self.n_rows // self.chunk

    def rows(self, key, rows: int, device=None) -> jax.Array:
        """``rows`` fresh rows from ``key``, made on ``device`` (the default
        device where None)."""
        arrays = self.shape_arrays
        if device is not None:
            if device not in self._on_device:
                self._on_device[device] = jax.device_put(arrays, device)
            arrays = self._on_device[device]
            key = jax.device_put(key, device)
        return _rows(key, *arrays, rows=rows, noise=self.noise,
                     zipf_a=self.zipf_a)

    def chunk_rows(self, c: int, device=None) -> jax.Array:
        """Rows ``[c * chunk, (c + 1) * chunk)`` of the catalog, made on
        ``device`` (the default device where None)."""
        return self.rows(jax.random.fold_in(self._key, c + 1), self.chunk,
                         device)

    def users(self, seed: int, n_users: int) -> np.ndarray:
        """User preference vectors, drawn like catalog rows (same
        centroids), from a stream of their own."""
        return np.asarray(self.rows(_stream(seed, "users"), n_users))


class ZipfStream:
    """Copy of ``repro.data.sampler.ZipfianQueryStream``: request ``i`` is
    the vector of a bounded-Zipf-ranked user plus Gaussian jitter."""

    def __init__(self, users: np.ndarray, *, zipf_a: float, jitter: float,
                 seed: int):
        self.users = np.asarray(users, dtype=np.float32)
        if zipf_a <= 0:
            raise ValueError(f"zipf_a must be > 0, got {zipf_a}")
        self.zipf_a = float(zipf_a)
        self.jitter = float(jitter)
        self._rng = np.random.default_rng([int(seed), 0x51])

    def sample(self, count: int) -> tuple[np.ndarray, np.ndarray]:
        """(user ranks (count,), queries (count, d) float32)."""
        n_users = self.users.shape[0]
        u = self._rng.uniform(1e-6, 1.0, size=count)
        ranks = np.clip(u ** (-1.0 / self.zipf_a) - 1.0, 0,
                        n_users - 1).astype(np.int64)
        q = self.users[ranks]
        if self.jitter > 0:
            q = q + self.jitter * self._rng.standard_normal(
                q.shape).astype(np.float32)
        return ranks, q.astype(np.float32)


# ----------------------------------------------------------------- weights
def _abs_topk_dense(pre, k):
    _, idx = jax.lax.top_k(jnp.abs(pre), k)
    rows = jnp.arange(pre.shape[0])[:, None]
    return jnp.zeros_like(pre).at[rows, idx].set(pre[rows, idx])


def _cos_loss(x, x_hat):
    num = jnp.sum(x * x_hat, axis=-1)
    den = jnp.linalg.norm(x, axis=-1) * jnp.linalg.norm(x_hat, axis=-1)
    return jnp.mean(1.0 - num / jnp.maximum(den, 1e-8))


def _sae_loss(params, x, k, aux_k):
    xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-8)
    pre = xn @ params["w_enc"] + params["b_enc"]
    return (_cos_loss(x, _abs_topk_dense(pre, k) @ params["w_dec"])
            + _cos_loss(x, _abs_topk_dense(pre, aux_k) @ params["w_dec"]))


def train_weights(catalog: Catalog, seed: int, cfg: dict) -> dict:
    """CompresSAE params {w_enc (d, h), b_enc (h,), w_dec (h, d)}, float32,
    made on the device in one jitted call from the seed."""
    d, h, k = int(cfg["d"]), int(cfg["h"]), int(cfg["k"])
    tr = cfg["training"]
    steps, batch = int(tr["steps"]), int(tr["batch"])
    lr, aux_k = float(tr["lr"]), k * int(tr["aux_k_mult"])
    b1, b2, eps = 0.9, 0.999, 1e-8
    key = _stream(seed, "weights")

    @jax.jit
    def run(key, centroids, spectrum):
        w_dec = jax.random.normal(jax.random.fold_in(key, 0), (h, d))
        w_dec = w_dec / jnp.linalg.norm(w_dec, axis=-1, keepdims=True)
        params = {"w_enc": w_dec.T, "b_enc": jnp.zeros((h,)), "w_dec": w_dec}
        zeros = jax.tree.map(jnp.zeros_like, params)

        def step(i, carry):
            p, m, v = carry
            x = _rows(jax.random.fold_in(key, i + 1), centroids, spectrum,
                      rows=batch, noise=catalog.noise,
                      zipf_a=catalog.zipf_a)
            g = jax.grad(_sae_loss)(p, x, k, aux_k)
            t = (i + 1).astype(jnp.float32)
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            p = jax.tree.map(
                lambda p, m, v: p - lr * (m / (1 - b1 ** t))
                / (jnp.sqrt(v / (1 - b2 ** t)) + eps), p, m, v)
            w = p["w_dec"]
            p = {**p, "w_dec": w / jnp.maximum(
                jnp.linalg.norm(w, axis=-1, keepdims=True), 1e-8)}
            return p, m, v

        params, _, _ = jax.lax.fori_loop(0, steps, step,
                                         (params, zeros, zeros))
        return params

    params = run(key, *catalog.shape_arrays)
    jax.block_until_ready(params)
    return params
