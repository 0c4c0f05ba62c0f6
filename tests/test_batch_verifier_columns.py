"""`BatchVerifier.prepare` builds a round's operands by columns (PR 28):
they are compared, array for array, with the row loop it ran before,
which is kept here as the reference. The verifier's table lookup and its
dispatch are replaced by recorders, so nothing compiles and the module
stays in the quick tier (tests/test_batch_verifier.py, whose tests run
device programs, is marked slow as a whole: tests/conftest.py)."""

import dataclasses

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519 as host
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem

# min_device_batch=0: a one-row round takes the device path too
_verifier = BatchVerifier(min_device_batch=0)


def _keypairs(n, seed):
    return [host.PrivKey.from_secret(seed + bytes([i])) for i in range(n)]


def _operands_by_rows(items, b):
    """The row loop `BatchVerifier.prepare` ran until PR 28, kept as the
    reference: (rb, sb, kb, pub, s_ok, well_formed) for bucket `b`."""
    rb = np.zeros((b, 32), dtype=np.uint8)
    sb = np.zeros((b, 32), dtype=np.uint8)
    kb = np.zeros((b, 32), dtype=np.uint8)
    pub = np.zeros((b, 32), dtype=np.uint8)
    s_ok = np.zeros(b, dtype=bool)
    well_formed = []
    for i, it in enumerate(items):
        if len(it.pubkey) != 32 or len(it.sig) != 64:
            continue  # leave row zeroed; s_ok stays False -> reject
        r, s = it.sig[:32], it.sig[32:]
        k = host.challenge(r, it.pubkey, it.msg)
        kb[i] = np.frombuffer(k.to_bytes(32, "little"), dtype=np.uint8)
        rb[i] = np.frombuffer(r, dtype=np.uint8)
        sb[i] = np.frombuffer(s, dtype=np.uint8)
        pub[i] = np.frombuffer(it.pubkey, dtype=np.uint8)
        s_ok[i] = int.from_bytes(s, "little") < host.L
        well_formed.append(i)
    return rb, sb, kb, pub, s_ok, well_formed


@pytest.fixture(scope="module")
def signed_rows():
    """12 genuine (pubkey, msg, sig) rows under 12 keys."""
    rows = []
    for i, k in enumerate(_keypairs(12, seed=b"cols")):
        msg = b"precommit sign-bytes %d " % i + bytes(range(90 + i % 7))
        rows.append(SigItem(k.public_key().data, msg, k.sign(msg)))
    return rows


def _with_s(it, s: int):
    return dataclasses.replace(it, sig=it.sig[:32] + s.to_bytes(32, "little"))


def _random_rows(n, seed):
    rng = np.random.default_rng(seed)
    return [
        SigItem(
            rng.bytes(32), rng.bytes(110 + i % 7),
            # the top byte of s on either side of L's 0x10, and at it
            rng.bytes(63) + bytes([(0x05, 0x10, 0x11, 0x0F)[i % 4]]),
        )
        for i in range(n)
    ]


def _bad_rows(kind):
    """The benchmark's four bad-row kinds (benchmark/harness/fixtures.py),
    planted at rows 2 and 9 of the genuine twelve."""

    def build(rows):
        out = list(rows)
        for i in (2, 9):
            sig = rows[i].sig
            if kind == "flipped_bit":
                sig = bytes([sig[0] ^ 0x04]) + sig[1:]
            elif kind == "wrong_key":
                sig = rows[(i + 1) % len(rows)].sig
            elif kind == "s_ge_L":
                s = int.from_bytes(sig[32:], "little") + host.L
                sig = sig[:32] + s.to_bytes(32, "little")
            elif kind == "short_sig":
                sig = sig[:63]
            out[i] = SigItem(rows[i].pubkey, rows[i].msg, sig)
        return out

    return build


def _resized(field, size):
    """Rows 0, 5 and 11 with `field` cut or stretched to `size` bytes."""

    def build(rows):
        out = list(rows)
        for i in (0, 5, 11):
            value = (getattr(rows[i], field) + b"\x07")[:size]
            out[i] = dataclasses.replace(rows[i], **{field: value})
        return out

    return build


def _scalars(*values):
    """Genuine rows whose s is replaced by each of `values` in turn."""
    return lambda rows: [
        _with_s(rows[i % len(rows)], s) for i, s in enumerate(values)
    ] + list(rows)


def _as_buffers(wrap):
    return lambda rows: [
        SigItem(wrap(it.pubkey), wrap(it.msg), wrap(it.sig)) for it in rows
    ]


_L = host.L
PREPARE_CASES = {
    "all-good": list,
    **{kind: _bad_rows(kind) for kind in
       ("flipped_bit", "wrong_key", "s_ge_L", "short_sig")},
    **{f"sig-{size}-bytes": _resized("sig", size) for size in (0, 63, 65)},
    **{f"pubkey-{size}-bytes": _resized("pubkey", size)
       for size in (0, 31, 33)},
    "s-eq-L": _scalars(_L),
    "s-eq-L-minus-1": _scalars(_L - 1),
    # same bytes as L but the least significant one, below and above
    "s-differs-in-byte-0": _scalars(_L - 0xED, _L + 1, _L + 0x12),
    # same bytes as L but the most significant one, below and above
    "s-differs-in-byte-31": _scalars(
        _L - (0x10 << 248), _L - (0x01 << 248), _L + (0x01 << 248),
        _L + (0xEF << 248),
    ),
    "s-zero-and-all-ones": _scalars(0, (1 << 256) - 1),
    "empty-message": lambda rows: [
        SigItem(it.pubkey, b"", it.sig) for it in rows[:3]
    ] + list(rows[3:]),
    "message-of-2000-bytes": lambda rows: list(rows[:6]) + [
        SigItem(rows[6].pubkey, bytes(range(250)) * 8, rows[6].sig)
    ] + list(rows[7:]),
    "bytearray-fields": _as_buffers(bytearray),
    "memoryview-fields": _as_buffers(memoryview),
    **{f"n-{n}": (lambda n: lambda rows: _random_rows(n, n))(n)
       for n in (1, 8, 127, 128, 129)},
    "n-300-off-the-rungs": lambda rows: _random_rows(300, 300),
    "n-600-big-tier-one-short": lambda rows: (
        _random_rows(599, 600) + _bad_rows("short_sig")(rows)[2:3]
    ),
}


def _spied(verifier, monkeypatch, snap):
    """`verifier` with its lookup and its dispatch replaced by recorders:
    prepare(...).run() then shows the operands a program would get and
    compiles nothing. `snap` False plays a store that cannot hold the
    round (the generic fallback)."""
    seen = {}

    def lookup(cache, items, rows, b, n):
        seen["well_formed"] = rows
        return ("tables", "valid", np.zeros(b, np.int32)) if snap else None

    def dispatch(fn, tier, b, n, *args, devices=1):
        seen.update(tier=tier, b=b, args=[
            a if isinstance(a, str) else np.asarray(a) for a in args
        ])
        return np.zeros(b, dtype=bool)

    monkeypatch.setattr(verifier, "_table_lookup", lookup)
    monkeypatch.setattr(verifier, "_dispatch", dispatch)
    return seen


def _as_bytes(items):
    return [
        SigItem(bytes(it.pubkey), bytes(it.msg), bytes(it.sig))
        for it in items
    ]


@pytest.mark.parametrize("case", PREPARE_CASES)
def test_prepare_by_columns_matches_the_row_loop(
    case, signed_rows, monkeypatch
):
    """rb, sb, kb, s_ok and the well-formed rows that `prepare` hands
    the program are, array for array, what the row loop built."""
    items = PREPARE_CASES[case](signed_rows)
    seen = _spied(_verifier, monkeypatch, snap=True)
    out = _verifier.prepare(items).run()
    assert out.shape == (len(items),)
    b = _verifier._registry.bucket_for(len(items))
    big = b >= _verifier._bigtable_min
    assert seen["tier"] == ("big" if big else "small")
    want = _operands_by_rows(_as_bytes(items), b)
    _tables, _valid, _idx, rb, sb, kb, s_ok = seen["args"]
    for name, got, ref in zip(
        ("rb", "sb", "kb", "s_ok"), (rb, sb, kb, s_ok),
        (want[0], want[1], want[2], want[4]),
    ):
        assert got.dtype == ref.dtype and got.shape == ref.shape, name
        assert np.array_equal(got, ref), name
    assert seen["well_formed"] == want[5]
    # the lookup indexes a list with them, once a row
    assert all(type(i) is int for i in seen["well_formed"])


def test_prepare_of_malformed_rows_only_returns_early(monkeypatch):
    """Every row malformed: all False, and neither a store nor a
    program is touched."""
    seen = _spied(_verifier, monkeypatch, snap=True)
    items = [
        SigItem(b"\x01" * 32, b"m", b"\x02" * 63),
        SigItem(b"\x01" * 31, b"m", b"\x02" * 64),
        SigItem(b"", b"", b""),
    ] * 3
    out = _verifier.prepare(items).run()
    assert out.tolist() == [False] * 9 and seen == {}


@pytest.mark.parametrize("device_hash", [False, True],
                         ids=["host-hash", "device-hash"])
def test_generic_fallback_operands_match_the_row_loop(
    device_hash, signed_rows, monkeypatch
):
    """A round the store cannot hold goes to the generic program with
    the keys and the host's challenges, whichever path hashed before."""
    v = BatchVerifier(
        min_device_batch=0, bigtable_min=0,
        device_challenge_min=0 if device_hash else None,
    )
    items = _bad_rows("short_sig")(signed_rows)
    seen = _spied(v, monkeypatch, snap=False)
    v.prepare(items).run()
    b = v._registry.bucket_for(len(items))
    rb, sb, kb, pub, s_ok, well_formed = _operands_by_rows(items, b)
    assert seen["tier"] == "generic"
    for got, ref in zip(seen["args"], (pub, rb, sb, kb, s_ok)):
        assert got.dtype == ref.dtype and np.array_equal(got, ref)
    assert seen["well_formed"] == well_formed == [
        i for i in range(12) if i not in (2, 9)
    ]


def test_device_hash_buffers_match_the_row_loop(signed_rows, monkeypatch):
    """The fused-hash path pads R || A || M of the well-formed rows and
    nothing of the others."""
    from tendermint_tpu.ops import sha512 as dev_sha512

    v = BatchVerifier(
        min_device_batch=0, bigtable_min=0, device_challenge_min=0
    )
    items = _bad_rows("short_sig")(signed_rows)
    seen = _spied(v, monkeypatch, snap=True)
    v.prepare(items).run()
    b = v._registry.bucket_for(len(items))
    rb, sb, _kb, _pub, s_ok, well_formed = _operands_by_rows(items, b)
    msgs, prefixes = [b""] * b, [b""] * b
    for i in well_formed:
        msgs[i] = items[i].msg
        prefixes[i] = items[i].sig[:32] + items[i].pubkey
    msg_buf, n_blocks = dev_sha512.pad_messages(msgs, prefix_pairs=prefixes)
    assert seen["tier"] == "big_msgs"
    for got, ref in zip(
        seen["args"][3:], (rb, sb, msg_buf, n_blocks, s_ok)
    ):
        assert got.dtype == ref.dtype and np.array_equal(got, ref)
