"""work_by_key.py's count of a secp256k1 verification, and the reader
that divides a key type's work by its programs' time."""

import pytest

import work
import work_by_key


def test_secp256k1_count_is_written_out():
    muls = 256 * 7 + 64 * 11 + 64 * 16 + (7 * 7 + 7 * 16) + (255 + 15 + 2)
    assert muls == 3953
    assert work_by_key.SECP_MULS_PER_SIGNATURE == muls
    assert work_by_key.SECP_OPS_PER_SIGNATURE == muls * (2048 + 128)
    assert work_by_key.SECP_BYTES_PER_SIGNATURE == 64 * 96 + 161


def test_ed25519_is_work_py_unchanged():
    assert work_by_key.PER_SIGNATURE["ed25519"] == (
        work.OPS_PER_SIGNATURE, work.BYTES_PER_SIGNATURE
    )
    assert work_by_key.least_seconds(16384, "TPU v5 lite") == (
        work.least_seconds(16384, "TPU v5 lite")
    )


def test_secp256k1_least_time_is_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    seconds, bound = work_by_key.least_seconds(
        4096, "TPU v5 lite", "secp256k1"
    )
    by_ops = 4096 * work_by_key.SECP_OPS_PER_SIGNATURE / peak[
        "int8_ops_per_s"
    ]
    by_bytes = 4096 * work_by_key.SECP_BYTES_PER_SIGNATURE / peak[
        "hbm_bytes_per_s"
    ]
    assert seconds == max(by_ops, by_bytes)
    assert bound == "compute"


@pytest.mark.parametrize("call", [
    lambda: work_by_key.least_seconds(1, "TPU v5 lite", "sr25519"),
    lambda: work_by_key.least_seconds(1, "cpu", "secp256k1"),
])
def test_unknown_key_type_or_device_is_an_error(call):
    with pytest.raises(KeyError):
        call()


def test_reader_counts_the_rows_of_its_key_type():
    from readers import kernel_roofline, kernel_roofline_by_key

    ctx = {
        "trace": {"modules": {
            "jit__verify_cached_big": [4, 0.4],
            "jit_verify_prehashed": [4, 0.2],
        }},
        "traced_rows": 65536,
        "traced_rows_by_key_type": {"ed25519": 49152, "secp256k1": 16384},
        "device": {"kind": "TPU v5 lite"},
    }
    ed = {"programs": "^jit__verify_cached_", "key_type": "ed25519"}
    assert kernel_roofline_by_key.read(ctx, ed) == pytest.approx(
        kernel_roofline.read(ctx, ed)
    )
    secp = {"programs": "^jit_verify_prehashed$", "key_type": "secp256k1"}
    least, _ = work_by_key.least_seconds(
        16384, "TPU v5 lite", "secp256k1"
    )
    assert kernel_roofline_by_key.read(ctx, secp) == pytest.approx(
        100 * least / 0.2
    )
    ctx["traced_rows_by_key_type"] = {"ed25519": 65536}
    assert kernel_roofline_by_key.read(ctx, secp) is None
    ctx["trace"] = None  # nothing to read: left out, never 0
    assert kernel_roofline_by_key.read(ctx, ed) is None
