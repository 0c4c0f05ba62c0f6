"""The work of one ed25519 verification, counted once for every tier,
bucket and window width.

The roofline reads the same work whatever implements it. So the count
is per REQUESTED signature (padding rows are not work), by the cheapest
method the repository has today, the doubling-free path over
precomputed fixed-window tables:

  verify  [s]B + [k](-A) == R   with s, k of 256 bits

  * Both scalars are cut into 64 radix-16 windows. [s]B and [k](-A)
    are each the sum of 64 table entries (one per window, the table
    holding every multiple the window can select), so no doubling is
    left: 64 + 64 = 128 point additions.
  * One point addition in extended coordinates with a precomputed
    (y-x, y+x, 2dt) entry costs 7 field multiplications (8 with a
    general z; the tables are affine, z = 1). Field additions are left
    out: they are a few per cent of a multiplication.
  * One compression of the result: an inversion by the fixed addition
    chain for p - 2 (254 squarings + 11 multiplications = 265 field
    multiplications) and 2 multiplications more for x and y.
  * A field element is 32 bytes, and a field multiplication done on
    an int8 unit is the 32 x 32 schoolbook product of its byte limbs:
    1,024 multiply-accumulates = 2,048 operations. The reduction mod
    2^255 - 19 folds 32 high limbs back with one more pass of 32
    multiply-accumulates (64 operations).

  operations = (128 * 7 + 265 + 2) * (2048 + 64) = 1163 * 2112

  bytes: what has to cross the memory bus for one signature. Its own
  96 bytes (R, s, k) in and 1 byte out, and the 128 table entries it
  adds: 3 field elements of 32 bytes each = 96 bytes an entry. The
  base-point table is shared by every row and stays on chip; the
  key's table is read from memory, 64 entries. So 64 * 96 + 97.

A batch-verification algorithm (a random linear combination and one
multi-scalar multiplication) does less work than this for a whole
batch. A PR that brings one has to be preceded by a `benchmark` issue
that revises this count, or its roofline share reads too high.
"""

from __future__ import annotations

import json
import os

POINT_ADDS = 128
MULS_PER_ADD = 7
MULS_PER_COMPRESS = 265 + 2
OPS_PER_MUL = 2 * 32 * 32 + 2 * 32
TABLE_ENTRY_BYTES = 3 * 32
KEY_TABLE_ENTRIES = 64
ROW_IO_BYTES = 96 + 1

OPS_PER_SIGNATURE = (
    POINT_ADDS * MULS_PER_ADD + MULS_PER_COMPRESS
) * OPS_PER_MUL
BYTES_PER_SIGNATURE = KEY_TABLE_ENTRIES * TABLE_ENTRY_BYTES + ROW_IO_BYTES


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device that is not in the table is
    an error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json"
        )
    return table[device_kind]


def least_seconds(signatures: int, device_kind: str) -> tuple:
    """(seconds, which bound holds): the least time the chip could take
    for that many verifications."""
    peak = peaks(device_kind)
    by_ops = signatures * OPS_PER_SIGNATURE / peak["int8_ops_per_s"]
    by_bytes = signatures * BYTES_PER_SIGNATURE / peak["hbm_bytes_per_s"]
    return (by_ops, "compute") if by_ops >= by_bytes else (by_bytes, "memory")
