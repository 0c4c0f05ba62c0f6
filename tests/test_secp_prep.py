"""The secp256k1 host half by columns (crypto/secp_native.py) and the
route of a mixed batch's secp256k1 rows (crypto/batch_verifier.py).

`prep_digest_batch` is the one implementation of the consensus rules
that both engines read: it is held here row by row to
`prep_digest_item` and to the rules written out plainly, over every
kind of row it refuses and over keys its KeyCache holds (a decompressed
point and a cached None); the native step in chunks is held to one call
and to the pure-Python ladder."""

from __future__ import annotations

import hashlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519, secp_native, sr25519
from tendermint_tpu.crypto import secp256k1 as host
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, SigItem
from tendermint_tpu.obs import tracer as tracer_mod
from tendermint_tpu.obs.tracer import Tracer

N = host.N


def _no_sqrt_key() -> bytes:
    x = next(
        x for x in range(5, 100)
        if pow((x**3 + 7) % host.P, (host.P - 1) // 2, host.P) != 1
    )
    return b"\x02" + x.to_bytes(32, "big")


def _rows():
    """(name, pubkey, digest, sig): every kind of row the rules refuse,
    valid rows under three keys, and repeats of a good and a bad key."""
    privs = [host.PrivKey.from_secret(b"prep%d" % i) for i in range(3)]
    pubs = [p.public_key().data for p in privs]
    out = []
    for i, pv in enumerate(privs):
        d = hashlib.sha256(b"msg%d" % i).digest()
        out.append(("valid%d" % i, pubs[i], d, host.sign_digest(d, pv.secret)))
    d = hashlib.sha256(b"m").digest()
    sig = host.sign_digest(d, privs[0].secret)
    r, s = sig[:32], int.from_bytes(sig[32:], "big")
    out += [
        ("r_zero", pubs[0], d, bytes(32) + sig[32:]),
        ("r_eq_n", pubs[0], d, N.to_bytes(32, "big") + sig[32:]),
        ("r_above_n", pubs[0], d, (N + 5).to_bytes(32, "big") + sig[32:]),
        ("s_zero", pubs[0], d, r + bytes(32)),
        ("high_s", pubs[0], d, r + (N - s).to_bytes(32, "big")),
        ("sig_63", pubs[0], d, sig[:63]),
        ("bad_prefix", b"\x05" + pubs[0][1:], d, sig),
        ("no_sqrt", _no_sqrt_key(), d, sig),
        ("repeat_good", pubs[0], d, sig),
        ("repeat_bad", _no_sqrt_key(), d, sig),
        ("wrong_digest", pubs[1], hashlib.sha256(b"x").digest(), sig),
    ]
    return out


def _plain(pub: bytes, digest: bytes, sig: bytes):
    """The rules written out: (r, point, u1, u2) or None."""
    if len(sig) != 64:
        return None
    r = int.from_bytes(sig[:32], "big")
    s = int.from_bytes(sig[32:], "big")
    if not (0 < r < N and 0 < s <= N // 2):
        return None
    pt = host.decompress_point(pub)
    if pt is None:
        return None
    w = pow(s, N - 2, N)
    return r, pt, int.from_bytes(digest, "big") * w % N, r * w % N


def _batch_row(b, i):
    hit = np.flatnonzero(b.rows == i)
    if not len(hit):
        return None
    j = int(hit[0])
    q = b.q[j].tobytes()
    return (
        int.from_bytes(b.r[j].tobytes(), "big"),
        (int.from_bytes(q[:32], "big"), int.from_bytes(q[32:], "big")),
        int.from_bytes(b.u1[j].tobytes(), "big"),
        int.from_bytes(b.u2[j].tobytes(), "big"),
    )


def test_batch_prep_equals_the_item_and_the_plain_rules_row_by_row():
    rows = _rows()
    pubs, digests, sigs = ([r[k] for r in rows] for k in (1, 2, 3))
    keys = secp_native.KeyCache()
    first = secp_native.prep_digest_batch(pubs, digests, sigs, keys)
    again = secp_native.prep_digest_batch(pubs, digests, sigs, keys)
    assert first.n == again.n == len(rows)
    for i, (name, pub, digest, sig) in enumerate(rows):
        want = _plain(pub, digest, sig)
        assert secp_native.prep_digest_item(pub, digest, sig) == want, name
        assert _batch_row(first, i) == want, name
        assert _batch_row(again, i) == want, name
    refused = {name for i, (name, *_) in enumerate(rows)
               if _batch_row(first, i) is None}
    assert refused == {
        "r_zero", "r_eq_n", "r_above_n", "s_zero", "high_s", "sig_63",
        "bad_prefix", "no_sqrt", "repeat_bad",
    }
    # the key is looked up only for rows the signature rules let
    # through: valid0-2, bad_prefix, no_sqrt, repeat_good, repeat_bad,
    # wrong_digest; five distinct keys, the bad ones kept as None
    assert (first.decompressed, first.cached) == (5, 3)
    assert (again.decompressed, again.cached) == (0, 8)
    assert len(keys) == 5


def test_native_step_in_chunks_gives_the_verdicts_of_one_call(monkeypatch):
    """The native step cut into chunks on the pool's threads answers as
    one call and as the pure-Python ladder do, bad rows among them."""
    privs = [host.PrivKey.from_secret(b"chunk%d" % i) for i in range(5)]
    pubs, digests, sigs, want = [], [], [], []
    for i in range(70):
        d = hashlib.sha256(b"c%d" % i).digest()
        sig = host.sign_digest(d, privs[i % 5].secret)
        bad = i % 9 == 4
        if bad:
            d = hashlib.sha256(b"other").digest()
        pubs.append(privs[i % 5].public_key().data)
        digests.append(d)
        sigs.append(sig if i % 13 else sig[:63])
        want.append(not bad and i % 13 != 0)
    b = secp_native.prep_digest_batch(pubs, digests, sigs)
    one = secp_native.start(b)()
    monkeypatch.setattr(secp_native, "CHUNK_ROWS_MIN", 8)
    monkeypatch.setattr(secp_native, "HOST_THREADS", 3)
    inline = secp_native.start(b)()
    with ThreadPoolExecutor(3) as pool:
        chunked = secp_native.start(b, pool)()
    monkeypatch.setattr(secp_native, "native_lib", lambda: None)
    python = secp_native.start(b)()
    assert one.tolist() == inline.tolist() == chunked.tolist() == want
    assert python.tolist() == want


def test_key_cache_is_bounded_and_gives_the_oldest_up():
    keys = secp_native.KeyCache(capacity=2)
    pubs = [host.PrivKey.from_secret(b"kc%d" % i).public_key().data
            for i in range(3)]
    for p in pubs:
        assert keys.decompress(p) is not None
    assert len(keys) == 2
    assert pubs[0] not in keys._points and pubs[2] in keys._points


def test_key_cache_shared_by_threads_stays_bounded_and_exact():
    """The prep thread and the dispatch thread share a verifier's cache:
    eight threads preparing rows under more keys than it holds, the
    interpreter switching often, get the rules' answer every time."""
    import sys
    import threading

    privs = [host.PrivKey.from_secret(b"st%d" % i) for i in range(12)]
    d = hashlib.sha256(b"m").digest()
    pubs = [p.public_key().data for p in privs] + [_no_sqrt_key()]
    sigs = [host.sign_digest(d, p.secret) for p in privs] + [
        host.sign_digest(d, privs[0].secret)
    ]
    want = [_plain(p, d, s) for p, s in zip(pubs, sigs)]
    keys = secp_native.KeyCache(capacity=5)
    errors = []

    def work(k):
        order = list(range(len(pubs)))[k:] + list(range(len(pubs)))[:k]
        for _ in range(20):
            b = secp_native.prep_digest_batch(
                [pubs[i] for i in order], [d] * len(order),
                [sigs[i] for i in order], keys,
            )
            got = [_batch_row(b, j) for j in range(len(order))]
            if got != [want[i] for i in order]:
                errors.append(k)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == [] and len(keys) <= 5


def test_python_ladder_gives_the_native_steps_verdicts(monkeypatch):
    rows = _rows()
    b = secp_native.prep_digest_batch(
        *([r[k] for r in rows] for k in (1, 2, 3))
    )
    native = secp_native.start(b)()
    monkeypatch.setattr(secp_native, "native_lib", lambda: None)
    assert secp_native.start(b)().tolist() == native.tolist()
    want = [
        p is not None and host.verify_digest(d, s, p[1])
        for p, d, s in ((_plain(*r[1:]), r[2], r[3]) for r in rows)
    ]
    assert native.tolist() == want
    assert sum(want) == 4  # valid0-2 and repeat_good


# --- the route of a mixed batch ---------------------------------------------


@pytest.fixture
def ring(monkeypatch):
    ring = Tracer(enabled=True)
    monkeypatch.setattr(tracer_mod, "_default", ring)
    return ring


def _mixed(n_secp: int):
    """Three ed25519 rows (below min_device_batch: the host path), one
    sr25519 row, and n_secp secp256k1 rows under eight keys, every
    fifth one corrupted; the expected bitmap."""
    items, want = [], []
    for i in range(3):
        k = ed25519.PrivKey.from_secret(b"ed%d" % i)
        items.append(SigItem(k.public_key().data, b"e%d" % i,
                             k.sign(b"e%d" % i)))
        want.append(True)
    kr = sr25519.PrivKey.from_secret(b"sr")
    items.append(SigItem(kr.public_key().data, b"sr", kr.sign(b"sr"),
                         "sr25519"))
    want.append(True)
    privs = [host.PrivKey.from_secret(b"route%d" % i) for i in range(8)]
    for i in range(n_secp):
        pv = privs[i % 8]
        msg = b"row%d" % i
        sig = pv.sign(msg)
        if i % 5 == 4:
            sig = sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]
        items.insert(2 * i % len(items),
                     SigItem(pv.public_key().data, msg, sig, "secp256k1"))
        want.insert(2 * i % len(want), i % 5 != 4)
    return items, want


def test_mixed_batch_prepares_32_secp_rows_with_its_ed25519_rows(ring):
    """>= 32 secp256k1 rows: crypto.secp_prep on the prepare side (a
    second round finds every key cached) after the ed25519 rows'
    crypto.ed_prep, the native step on the run side, crypto.secp_verify
    from its start to its verdicts and outside no other span; the
    bitmap re-interleaved."""
    v = BatchVerifier()
    items, want = _mixed(40)
    prepared = v.prepare(items)
    assert [r.name for r in ring.records()] == [
        "crypto.ed_prep", "crypto.secp_prep"
    ]
    ed_prep, prep = ring.records()
    assert ed_prep.fields["rows"] == 3
    assert prep.fields["rows"] == 40
    assert (prep.fields["decompressed"], prep.fields["cached"]) == (8, 32)
    assert prepared.host_rows == 41
    assert prepared.run().tolist() == want
    assert v.prepare(items).run().tolist() == want
    assert [
        (r.fields["decompressed"], r.fields["cached"])
        for r in ring.records() if r.name == "crypto.secp_prep"
    ] == [(8, 32), (0, 40)]
    verifies = [r for r in ring.records() if r.name == "crypto.secp_verify"]
    assert len(verifies) == 2
    for r in verifies:
        assert r.fields["engine"] == "host"
        assert (r.fields["rows"], r.fields["rejected"]) == (40, 8)
        assert "parent" not in r.fields


def test_mixed_batch_keeps_31_secp_rows_in_one_host_call(ring):
    """Fewer than 32 secp256k1 rows: one host call inside the round,
    its crypto.secp_prep inside the round's crypto.secp_verify; the
    ed25519 rows prepared under crypto.ed_prep as before."""
    items, want = _mixed(31)
    prepared = BatchVerifier().prepare(items)
    assert [
        (r.name, r.fields["rows"]) for r in ring.records()
    ] == [("crypto.ed_prep", 3)]
    assert prepared.run().tolist() == want
    recs = {r.name: r for r in ring.records()}
    assert recs["crypto.secp_verify"].fields["engine"] == "host"
    assert recs["crypto.secp_prep"].fields["parent"] == "crypto.secp_verify"
    assert recs["crypto.secp_prep"].fields["rows"] == 31
