"""Operations and bytes that one call of each serving kernel needs, from
its shapes alone, and the least time the chip's peaks allow for it.

The counts are what the algorithm has to do, not what a kernel happens to
do: the index is read once per call whatever the query-panel height, and
the encoder weights once per call whatever the batch tile.  A kernel that
re-reads data therefore shows a lower roofline share, never a count that
moves with its tiling.
"""
from __future__ import annotations

F32 = 4


def shards(cfg: dict) -> int:
    """How many chips a configuration's catalog rows are split over (its
    ``shards`` key, 1 where it has none)."""
    return int(cfg.get("shards", 1))


def rows_per_chip(cfg: dict) -> int:
    """The index rows one chip holds and scans in each call."""
    return int(cfg["n_rows"]) // shards(cfg)


def code_bytes_per_row(fmt: str, k: int, h: int) -> int:
    """Stored bytes of one fixed-k code row in an index format:
    ``f32``: k float32 values + k int32 indices;
    ``q8``: k int8 values + k int16 indices (int32 once h >= 65536) +
    one float32 scale."""
    if fmt == "f32":
        return k * (F32 + 4)
    if fmt == "q8":
        return k * (1 + (2 if h < 65536 else 4)) + F32
    raise ValueError(f"unknown index format {fmt!r}")


def encode_cost(b: int, d: int, h: int, k: int) -> tuple[float, float]:
    """(flops, bytes) of encoding ``b`` dense rows into k-sparse codes:
    the (b, d) x (d, h) matmul, reading the queries, W_enc and b_enc and
    writing the (b, k) codes."""
    flops = 2.0 * b * d * h
    bytes_ = F32 * (b * d + d * h + h) + 2 * F32 * b * k
    return flops, bytes_


def scan_cost(fmt: str, n: int, b: int, k: int, h: int, top_n: int
              ) -> tuple[float, float]:
    """(flops, bytes) of scoring ``b`` query codes against ``n`` index rows
    and selecting each query's top ``top_n``: every index row (codes and
    its inverse norm) read once, the query codes read, the results
    written; a multiply-add per query per stored nonzero."""
    flops = 2.0 * b * n * k
    bytes_ = (n * (code_bytes_per_row(fmt, k, h) + F32)
              + 2 * F32 * b * k + 2 * F32 * b * top_n)
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The larger of compute time at the bf16 peak and memory time at the
    HBM bandwidth: no call can take less."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])


def serve_least_seconds(cfg: dict, rows: int, top_n: int, peak: dict
                        ) -> float:
    """The least time of one whole serve step of a ``rows``-row panel on
    one chip: encoding it (every chip encodes the whole panel), then one
    read of that chip's rows of the index to score and select it."""
    d, h, k = (int(cfg[x]) for x in ("d", "h", "k"))
    n = rows_per_chip(cfg)
    return (least_seconds(*encode_cost(rows, d, h, k), peak)
            + least_seconds(*scan_cost(cfg["index_format"], n, rows, k, h,
                                       top_n), peak))
