"""work.py's count and the table of peaks."""

import pytest

import work


def test_count_is_written_out():
    assert work.OPS_PER_SIGNATURE == (128 * 7 + 267) * (2048 + 64)
    assert work.BYTES_PER_SIGNATURE == 64 * 96 + 97


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.least_seconds(1024, "cpu")


def test_least_time_is_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    seconds, bound = work.least_seconds(16384, "TPU v5 lite")
    by_ops = 16384 * work.OPS_PER_SIGNATURE / peak["int8_ops_per_s"]
    by_bytes = 16384 * work.BYTES_PER_SIGNATURE / peak["hbm_bytes_per_s"]
    assert seconds == max(by_ops, by_bytes)
    assert bound == ("compute" if by_ops >= by_bytes else "memory")


def test_roofline_reader_counts_requested_rows_only():
    from readers import kernel_roofline, kernel_time

    ctx = {
        "trace": {"modules": {
            "jit__verify_cached_big": [4, 0.4],
            "jit_neg_pubkey_bigtable": [1, 9.0],
        }},
        "traced_rows": 65536,
        "device": {"kind": "TPU v5 lite"},
    }
    spec = {"programs": "^jit__verify_cached_"}
    assert kernel_time.read(ctx, spec) == pytest.approx(100.0)
    least, _ = work.least_seconds(65536, "TPU v5 lite")
    assert kernel_roofline.read(ctx, spec) == pytest.approx(
        100 * least / 0.4
    )
    ctx["trace"] = None  # nothing to read: left out, never 0
    assert kernel_roofline.read(ctx, spec) is None
    assert kernel_time.read(ctx, spec) is None
