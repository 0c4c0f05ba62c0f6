"""Committee kind `mixed_keys`: n validators of equal power, a share of
them holding secp256k1 keys and the rest ed25519 keys, every one of
them signing every commit, one validator set for the whole run
(BASELINE.json configs[3]: "mixed ed25519/secp256k1").

The configuration gives `validators` and `secp256k1_share`. Which
validators hold secp256k1 keys is drawn from the seed: round(share x n)
of them. Every secret is SHA-256(bench|<seed>|val|<i>): an ed25519 seed
as it is, a secp256k1 scalar reduced into [1, N). The set is ordered by
address as the program orders it: ed25519 by the first 20 bytes of
SHA-256 of the key, secp256k1 by RIPEMD160(SHA256(33-byte compressed
key)) (crypto/secp256k1/secp256k1.go:155-167). A secp256k1 validator
signs as the reference node does: ECDSA over SHA-256 of the sign-bytes
with RFC 6979's nonce, 64 bytes `R || S`, low S.

Keys, signatures and the window-wide reference go through OpenSSL
(`cryptography`); the plain references are `reference/ed25519_plain.py`
and `reference/secp256k1_plain.py`.

Bad rows of a secp256k1 validator are ed25519's with `high_s` (s -> N -
s: valid ECDSA, refused as malleable) in the place of `s_ge_L`.
Guarantees: `ed25519_equal`'s, and for a secp256k1 row a key that is
not a 33-byte point on the curve or a signature that is not 64 bytes
rejected, `r` and `s` in [1, N), `s > N/2` rejected (CONTROLS' `low_s`
drops this one).
"""

from __future__ import annotations

import hashlib
import random

from cryptography.hazmat.primitives import hashes, serialization
from cryptography.hazmat.primitives.asymmetric import ec
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)
from cryptography.hazmat.primitives.asymmetric.utils import (
    decode_dss_signature,
)

import committees
from committees import ed25519_equal
from reference import ed25519_plain, secp256k1_plain

ED, SECP = ed25519_equal.KEY_TYPE, "secp256k1"
BAD_KINDS = {
    ED: ed25519_equal.BAD_KINDS[ED],
    SECP: ("flipped_bit", "wrong_key", "high_s", "short_sig"),
}
CONTROLS = ("s_range", "low_s")
_ECDSA = ec.ECDSA(hashes.SHA256(), deterministic_signing=True)


def compressed(key) -> bytes:
    """The 33 bytes of a secp256k1 key's public point."""
    return key.public_key().public_bytes(
        serialization.Encoding.X962,
        serialization.PublicFormat.CompressedPoint,
    )


def secp_sign(key, msg: bytes) -> bytes:
    """64 bytes `R || S`, S folded low."""
    r, s = decode_dss_signature(key.sign(msg, _ECDSA))
    if s > secp256k1_plain.N // 2:
        s = secp256k1_plain.N - s
    return r.to_bytes(32, "big") + s.to_bytes(32, "big")


def corrupt(genuine: list, i: int, kind: str, key_type: str) -> bytes:
    """Row i's signature made one of its key type's four bad rows."""
    if kind == "high_s" and key_type == SECP:
        sig = genuine[i]
        s = secp256k1_plain.N - int.from_bytes(sig[32:], "big")
        return sig[:32] + s.to_bytes(32, "big")
    if kind not in BAD_KINDS[key_type]:
        raise ValueError(f"{kind} on a {key_type} row")
    return ed25519_equal.corrupt(genuine, i, kind)


class Committee(ed25519_equal.Committee):
    def __init__(self, seed: int, config: dict):
        n = int(config["validators"])
        share = float(config["secp256k1_share"])
        secp = set(
            random.Random(seed * 1_000_003 + 31).sample(
                range(n), round(share * n)
            )
        )
        rows = []
        for i in range(n):
            secret = ed25519_equal.key_seed(seed, i)
            if i in secp:
                key = ec.derive_private_key(
                    int.from_bytes(secret, "big") % (secp256k1_plain.N - 1)
                    + 1,
                    ec.SECP256K1(),
                )
                pub = compressed(key)
                rows.append((secp256k1_plain.address(pub), pub, SECP, key))
            else:
                key = Ed25519PrivateKey.from_private_bytes(secret)
                pub = key.public_key().public_bytes_raw()
                rows.append(
                    (hashlib.sha256(pub).digest()[:20], pub, ED, key)
                )
        self._seat(seed, sorted(rows, key=lambda row: row[0]))

    def bad_kinds(self, height: int, row: int) -> tuple:
        return BAD_KINDS[self._validators[row].key_type]

    def sign_commit(self, height: int, plan: dict) -> tuple:
        """Every validator precommits the block with its own scheme,
        then the rows in `plan` are made bad."""
        genuine = [
            secp_sign(k, m) if v.key_type == SECP else k.sign(m)
            for v, k, m in zip(
                self._validators, self.keys, self.sign_bytes(height)
            )
        ]
        sigs = list(genuine)
        for i, kind in plan.items():
            sigs[i] = corrupt(
                genuine, i, kind, self._validators[i].key_type
            )
        return height, sigs, plan

    def reference(self, commits: list, control: str = "") -> list:
        committees.check_control(control, CONTROLS)
        s_range, low_s = control != "s_range", control != "low_s"
        return [
            [
                secp256k1_plain.verify(v.pub, msg, sig, low_s)
                if v.key_type == SECP
                else ed25519_plain.verify(v.pub, msg, sig, s_range)
                for v, msg, sig in zip(
                    self._validators, self.sign_bytes(h), sigs
                )
            ]
            for h, sigs in commits
        ]

    def cross_check(self, rows: list, reference: dict) -> dict:
        """Each OpenSSL-backed reference held against its pure-Python
        one, on the sampled rows of its key type."""
        return {
            "rfc8032_vs_openssl": committees.cross_check_rows(
                self, rows, reference, ED, ed25519_plain.verify_rfc8032
            ),
            "secp256k1_plain_vs_openssl": committees.cross_check_rows(
                self, rows, reference, SECP, secp256k1_plain.verify_plain
            ),
        }
