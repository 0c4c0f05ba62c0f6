"""Committee kind `ed25519_equal`: n ed25519 validators of equal power,
every one of them signing every commit, one validator set for the whole
run. The kind of every configuration that names none.

Keys are the 32-byte seeds SHA-256(bench|<seed>|val|<i>); the set is
ordered by address, the first 20 bytes of SHA-256 of the public key.
Keys and signatures go through OpenSSL (`cryptography`), the plain
reference is `reference/ed25519_plain.py`.

Guarantees (the configurations' `guarantees`), each a rule of the
reference: cofactorless RFC 8032 verification with the encoded-point
comparison; a key that is not 32 bytes or a signature that is not 64
rejected; `s >= L` rejected (CONTROLS' `s_range` drops this one); a
commit stands on more than two thirds of the power.
"""

from __future__ import annotations

import hashlib

from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
)

import committees
from harness import fixtures
from reference import ed25519_plain

KEY_TYPE = "ed25519"
POWER = 10
BAD_KINDS = {KEY_TYPE: ("flipped_bit", "wrong_key", "s_ge_L", "short_sig")}
CONTROLS = ("s_range",)


def key_seed(seed: int, i: int) -> bytes:
    """The 32 secret bytes of validator i (before the set is ordered)."""
    return hashlib.sha256(b"bench|%d|val|%d" % (seed, i)).digest()


def corrupt(genuine: list, i: int, kind: str) -> bytes:
    """Row i's signature made one of the four bad rows."""
    sig = genuine[i]
    if kind == "flipped_bit":
        return bytes([sig[0] ^ 0x04]) + sig[1:]
    if kind == "wrong_key":  # valid, but under the next row's key
        return genuine[(i + 1) % len(genuine)]
    if kind == "s_ge_L":  # s + L is the same scalar mod L, out of range
        s = int.from_bytes(sig[32:], "little") + ed25519_plain.L
        return sig[:32] + s.to_bytes(32, "little")
    if kind == "short_sig":
        return sig[:63]
    raise ValueError(kind)


class Committee:
    def __init__(self, seed: int, config: dict):
        n = int(config["validators"])
        keys = [
            Ed25519PrivateKey.from_private_bytes(key_seed(seed, i))
            for i in range(n)
        ]
        self._seat(seed, sorted(
            (hashlib.sha256(pub).digest()[:20], pub, KEY_TYPE, key)
            for key in keys
            for pub in [key.public_key().public_bytes_raw()]
        ))

    def _seat(self, seed: int, rows: list) -> None:
        """`rows`: (address, public key, key type, private key) in
        validator-set order; everyone has the same power and signs."""
        self.seed = seed
        self.keys = [key for _, _, _, key in rows]
        self._validators = tuple(
            fixtures.Validator(key_type, pub, addr, POWER)
            for addr, pub, key_type, _ in rows
        )
        self._signers = list(range(len(rows)))
        self._powers = [POWER] * len(rows)

    def validators(self, height: int) -> tuple:
        return self._validators

    def signers(self, height: int) -> list:
        return self._signers

    def sign_bytes(self, height: int) -> list:
        return fixtures.sign_bytes(self.seed, height, self._signers)

    def bad_kinds(self, height: int, row: int) -> tuple:
        return BAD_KINDS[KEY_TYPE]

    def sign_commit(self, height: int, plan: dict) -> tuple:
        """Every validator precommits the block, then the rows in
        `plan` are made bad."""
        genuine = [
            k.sign(m) for k, m in zip(self.keys, self.sign_bytes(height))
        ]
        sigs = list(genuine)
        for i, kind in plan.items():
            sigs[i] = corrupt(genuine, i, kind)
        return height, sigs, plan

    def reference(self, commits: list, control: str = "") -> list:
        committees.check_control(control, CONTROLS)
        s_range = control != "s_range"
        return [
            [
                ed25519_plain.verify(v.pub, msg, sig, s_range)
                for v, msg, sig in zip(
                    self._validators, self.sign_bytes(h), sigs
                )
            ]
            for h, sigs in commits
        ]

    def quorum(self, height: int, valid: list) -> bool:
        return ed25519_plain.quorum(valid, self._powers)

    def cross_check(self, rows: list, reference: dict) -> dict:
        """The OpenSSL-backed reference held against the pure-Python
        RFC 8032 one."""
        return {
            "rfc8032_vs_openssl": committees.cross_check_rows(
                self, rows, reference, KEY_TYPE,
                ed25519_plain.verify_rfc8032,
            )
        }
