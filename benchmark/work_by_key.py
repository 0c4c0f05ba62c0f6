"""The work of one verification by key type: ed25519's count is
`work.py`'s, unchanged; this file adds the count of one secp256k1 ECDSA
verification, by the same conventions (per REQUESTED signature, the
cheapest method the repository has today, field additions left out,
each field multiplication as the 32 x 32 byte product of an int8 unit
plus its reduction).

  verify  x([u1]G + [u2]Q) mod n == r   with u1, u2 of 256 bits

  The method is the joint radix-16 ladder of `ops/secp256k1_kernel.py`
  (Straus-Shamir): 64 windows of 4 doublings and 2 additions, one entry
  of G's table and one of Q's. Formulas of the Explicit-Formulas
  Database for a = 0 in Jacobian coordinates, a squaring counted as a
  multiplication:

  * 256 doublings, dbl-2009-l: 2M + 5S = 7.
  * 64 additions of G's entries, affine and shared by every row,
    madd-2007-bl: 7M + 4S = 11.
  * 64 additions of Q's entries, Jacobian, add-2007-bl: 11M + 5S = 16.
  * Q's table of 16 multiples, built per row: 7 doublings and 7
    additions (even entries by doubling, odd by adding Q), 7 * 7 +
    7 * 16 = 161.
  * The affine x of the result: one inversion of Z by Fermat, the
    addition chain for p - 2 (255 squarings + 15 multiplications =
    270), then Z^-2 and X * Z^-2: 2 more. (ed25519's count also keeps
    one inversion per signature.)

  muls = 256 * 7 + 64 * 11 + 64 * 16 + 161 + 272 = 3953

  * A field multiplication: the 32 x 32 schoolbook product of byte
    limbs, 1,024 multiply-accumulates = 2,048 operations. The reduction
    mod p = 2^256 - 2^32 - 977 folds the 32 high limbs back times
    2^32 + 977: the 2^32 term is a shift (additions, left out), 977 =
    0x3D1 is two byte limbs, so 64 multiply-accumulates (128
    operations). The few limbs the fold carries past 2^256 are folded
    once more: left out, as field additions are.

  operations = 3953 * (2048 + 128)

  bytes: the row's own 161 (Q's affine x and y, 64; u1, u2 and r, 96;
  in, and 1 out) and the 64 entries of Q's table the ladder selects,
  3 field elements of 32 bytes each: 64 * 96 + 161. G's table is
  shared by every row and stays on chip, as ed25519's base table does.
"""

from __future__ import annotations

import work

SECP_DOUBLINGS = 256
SECP_MULS_PER_DOUBLING = 2 + 5
SECP_G_ADDS = 64
SECP_MULS_PER_MIXED_ADD = 7 + 4
SECP_Q_ADDS = 64
SECP_MULS_PER_ADD = 11 + 5
SECP_MULS_Q_TABLE = 7 * SECP_MULS_PER_DOUBLING + 7 * SECP_MULS_PER_ADD
SECP_MULS_AFFINE_X = 255 + 15 + 2
SECP_OPS_PER_MUL = 2 * 32 * 32 + 2 * 32 * 2
SECP_TABLE_ENTRIES = 64
SECP_ROW_IO_BYTES = 64 + 96 + 1

SECP_MULS_PER_SIGNATURE = (
    SECP_DOUBLINGS * SECP_MULS_PER_DOUBLING
    + SECP_G_ADDS * SECP_MULS_PER_MIXED_ADD
    + SECP_Q_ADDS * SECP_MULS_PER_ADD
    + SECP_MULS_Q_TABLE
    + SECP_MULS_AFFINE_X
)
SECP_OPS_PER_SIGNATURE = SECP_MULS_PER_SIGNATURE * SECP_OPS_PER_MUL
SECP_BYTES_PER_SIGNATURE = (
    SECP_TABLE_ENTRIES * work.TABLE_ENTRY_BYTES + SECP_ROW_IO_BYTES
)

# key type -> (operations, bytes) of one requested verification
PER_SIGNATURE = {
    "ed25519": (work.OPS_PER_SIGNATURE, work.BYTES_PER_SIGNATURE),
    "secp256k1": (SECP_OPS_PER_SIGNATURE, SECP_BYTES_PER_SIGNATURE),
}


def least_seconds(signatures: int, device_kind: str,
                  key_type: str = "ed25519") -> tuple:
    """(seconds, which bound holds): the least time the chip could take
    for that many verifications of one key type. A key type without a
    count is an error, never a default."""
    if key_type == "ed25519":
        return work.least_seconds(signatures, device_kind)
    ops, nbytes = PER_SIGNATURE[key_type]
    peak = work.peaks(device_kind)
    by_ops = signatures * ops / peak["int8_ops_per_s"]
    by_bytes = signatures * nbytes / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")
