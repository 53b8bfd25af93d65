"""Operation and byte counts of the serving kernels."""
import pytest

from yardstick import counts

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("fmt,quantized,want", [("q8", True, 100),
                                                ("f32", False, 256)])
def test_bytes_per_row_agree_with_the_size_table(fmt, quantized, want):
    from benchmarks.size_table import sparse_bytes_per_row

    got = counts.code_bytes_per_row(fmt, k=32, h=4096)
    assert got == want
    assert got == sparse_bytes_per_row(32, h=4096, quantized=quantized)


def test_q8_indices_widen_past_int16():
    assert counts.code_bytes_per_row("q8", k=32, h=65536) == 32 * 5 + 4


def test_scan_reads_the_index_once_per_call_whatever_the_panel():
    f8, b8 = counts.scan_cost("q8", 2 ** 20, 8, 32, 4096, 100)
    f64, b64 = counts.scan_cost("q8", 2 ** 20, 64, 32, 4096, 100)
    assert f8 == 2 * 8 * 2 ** 20 * 32 and f64 == 8 * f8
    index = 2 ** 20 * (100 + 4)   # codes and an f32 inverse norm per row
    assert b8 == index + 8 * 8 * 32 + 8 * 8 * 100
    assert b64 - b8 == 56 * (8 * 32 + 8 * 100)


def test_encode_counts_and_least_time():
    f, b = counts.encode_cost(64, 768, 4096, 32)
    assert f == 2 * 64 * 768 * 4096
    assert b == 4 * (64 * 768 + 768 * 4096 + 4096) + 8 * 64 * 32
    # 64 rows: the weights, not the flops, bound the call
    assert counts.least_seconds(f, b, PEAK) == pytest.approx(b / 819e9)
    assert counts.least_seconds(1e12, 1.0, PEAK) == pytest.approx(1 / 197)


def test_unknown_format_is_an_error():
    with pytest.raises(ValueError):
        counts.code_bytes_per_row("int4", 32, 4096)
