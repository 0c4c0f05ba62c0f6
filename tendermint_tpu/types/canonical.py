"""Canonical sign-bytes for votes and proposals.

Reference: types/canonical.go:18,57 + types/vote.go:95-103 — sign-bytes are
`protoio.MarshalDelimited(CanonicalVote{...})` where CanonicalVote uses
sfixed64 height/round (fixed-width so signing devices can parse offsets) and
a trailing chain_id. The per-vote timestamp makes every vote's message
unique — which is why the TPU verifier takes ragged per-vote messages
(SURVEY.md §7.3 hard part 4).

Timestamps are integer nanoseconds since the Unix epoch throughout the
framework; they encode here as protobuf Timestamp (seconds + nanos).
"""

from __future__ import annotations

import numpy as np

from ..libs import protoio as pio

# SignedMsgType values (reference types/signed_msg_type.go)
PREVOTE_TYPE = 1
PRECOMMIT_TYPE = 2
PROPOSAL_TYPE = 32

# google.protobuf.Timestamp's fields, and the CanonicalVote field that
# holds one: the row encoder (`vote_from_parts`) and the column encoder
# (`votes_from_parts`) both read them from here
TS_SECONDS_FIELD = 1
TS_NANOS_FIELD = 2
VOTE_TIMESTAMP_FIELD = 5
_NS_PER_S = 1_000_000_000

# The column encoder takes timestamps in [0, 2**63) ns: seconds below
# 2**35 and nanos below 2**30, so each varint is at most five 7-bit
# groups. Any other row is built by `vote_from_parts`.
_COLUMN_NS_LIMIT = 2**63
# Below this many rows in a call the column encoder's fixed numpy cost
# is more than what the rows cost one at a time. One commit, random
# nanos, on a CPU (Python 3.12, numpy 2.0), columns against rows: 80
# against 18 us at 4 rows, 83 / 34 at 8, 88 / 65 at 16, 106 / 102 at 24,
# 107 / 136 at 32, 120 / 261 at 64; a whole `verify_commit_light` is
# even at 24 rows and ahead by columns from 32.
COLUMN_MIN_ROWS = 32
_VARINT_SHIFTS = np.arange(0, 35, 7, dtype=np.int64)


def encode_timestamp(ns: int) -> bytes:
    seconds, nanos = divmod(ns, _NS_PER_S)
    return pio.field_varint(TS_SECONDS_FIELD, seconds) + pio.field_varint(
        TS_NANOS_FIELD, nanos
    )


def decode_timestamp(data: bytes) -> int:
    fields = pio.decode_fields(data)
    seconds = fields.get(TS_SECONDS_FIELD, [0])[0]
    nanos = fields.get(TS_NANOS_FIELD, [0])[0]
    return seconds * _NS_PER_S + nanos


def _varint_columns(v: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(byte lengths, [n, 5] uint8 bytes) of the uvarints of `v`, each
    0 <= v < 2**35; a length of 0 where v is 0, which proto3 leaves out
    (`pio.field_varint`)."""
    groups = v[:, None] >> _VARINT_SHIFTS
    length = np.count_nonzero(groups, axis=1)
    more = np.arange(5) < (length - 1)[:, None]
    return length, ((groups & 0x7F) | (more * 0x80)).astype(np.uint8)


def _vote_group(
    members: list,
    slot: np.ndarray,
    plen: int,
    slen: int,
    s_bytes: np.ndarray,
    n_bytes: np.ndarray,
) -> list[bytes]:
    """The sign-bytes of rows that share one layout: parts of `plen` /
    `slen` bytes (row j's is `members[slot[j]]`) and seconds / nanos
    varints of s_bytes / n_bytes' widths (0: the field is left out)."""
    ts = []  # the Timestamp's body: tags (bytes) and varint columns
    for field, col in ((TS_SECONDS_FIELD, s_bytes), (TS_NANOS_FIELD, n_bytes)):
        if col.shape[1]:
            ts += [pio.tag(field, pio.WIRE_VARINT), col]
    ts_len = sum(len(x) if isinstance(x, bytes) else x.shape[1] for x in ts)
    body = plen + ts_len + slen
    ts_head = pio.tag(VOTE_TIMESTAMP_FIELD, pio.WIRE_BYTES)
    ts_head += pio.write_uvarint(ts_len)
    body += len(ts_head)

    def part(chunks: list, w: int):
        if len(chunks) == 1:
            return chunks[0]
        table = np.frombuffer(b"".join(chunks), dtype=np.uint8)
        return table.reshape(len(chunks), w)[slot]

    # the row's pieces in order; adjacent constant bytes merged
    pieces: list = []
    for x in (
        pio.write_uvarint(body),  # marshal_delimited's length
        part([p for p, _ in members], plen),
        ts_head,
        *ts,
        part([s for _, s in members], slen),
    ):
        if isinstance(x, bytes) and pieces and isinstance(pieces[-1], bytes):
            pieces[-1] += x
        else:
            pieces.append(x)
    width = sum(len(x) if isinstance(x, bytes) else x.shape[1] for x in pieces)
    m = np.empty((len(slot), width), dtype=np.uint8)
    at = 0
    for x in pieces:
        if isinstance(x, bytes):
            x = np.frombuffer(x, dtype=np.uint8)
        m[:, at : at + x.shape[-1]] = x
        at += x.shape[-1]
    # one bytes object a row (a void scalar keeps trailing zero bytes)
    return m.view(np.dtype((np.void, width))).ravel().tolist()


def _canonical_part_set_header(total: int, hash_: bytes) -> bytes:
    return pio.field_varint(1, total) + pio.field_bytes(2, hash_)


def canonical_block_id(hash_: bytes, psh_total: int, psh_hash: bytes) -> bytes:
    """CanonicalBlockID; empty when the block id is nil (returns b'')."""
    if not hash_ and psh_total == 0 and not psh_hash:
        return b""
    return pio.field_bytes(1, hash_) + pio.field_message(
        2, _canonical_part_set_header(psh_total, psh_hash)
    )


class CanonicalVoteEncoder:
    """Stateless canonical encoders, exposed for privval/remote-signer
    compatibility checks."""

    @staticmethod
    def vote_parts(
        msg_type: int,
        height: int,
        round_: int,
        block_id_bytes: bytes,
        chain_id: str,
    ) -> tuple[bytes, bytes]:
        """(prefix, suffix) of the canonical vote body around its only
        per-signer field — the timestamp (field 5):
        vote(...) == marshal_delimited(prefix + field_message(5,
        encode_timestamp(ts)) + suffix). Exposed so batch commit
        verification can encode O(validators) sign-bytes per commit
        without re-encoding the shared fields (types/block.py caches
        these parts per commit); `vote` below composes the same parts,
        keeping one source of truth for the layout."""
        prefix = b"".join(
            [
                pio.field_varint(1, msg_type),
                pio.field_sfixed64(2, height),
                pio.field_sfixed64(3, round_),
                (
                    pio.field_message(4, block_id_bytes)
                    if block_id_bytes
                    else b""
                ),
            ]
        )
        return prefix, pio.field_bytes(6, chain_id.encode())

    @staticmethod
    def vote_from_parts(
        prefix: bytes, suffix: bytes, timestamp_ns: int
    ) -> bytes:
        """Assemble the final sign-bytes from vote_parts output. This and
        `votes_from_parts` are the ONLY places the timestamp field and
        the delimited framing are laid out, from the same constants, so
        cached-parts callers cannot drift from `vote`."""
        return pio.marshal_delimited(
            prefix
            + pio.field_message(
                VOTE_TIMESTAMP_FIELD, encode_timestamp(timestamp_ns)
            )
            + suffix
        )

    @staticmethod
    def votes_from_parts(
        parts: list, timestamps_ns: list, part_of_row=None
    ) -> tuple[list[bytes], int]:
        """`vote_from_parts` by columns, for many rows at once:
        (sign-bytes of each row, the number of rows built one at a time).

        Row j is `vote_from_parts(*parts[part_of_row[j]],
        timestamps_ns[j])`, byte for byte; `part_of_row` None means every
        row is of `parts[0]`. The rows of several commits go in one call,
        so the numpy work is paid once per call, not once per commit.

        Seconds and nanos are encoded as varint columns. Rows that share
        the byte lengths of their parts and of both varints form a group,
        built as one [rows, width] uint8 matrix (delimited length,
        prefix, field 5's tag and length, the timestamp's fields, suffix)
        and read out as one `bytes` a row. A timestamp outside
        [0, 2**63) ns goes to `vote_from_parts`, in its place, and so
        does every row of a call under COLUMN_MIN_ROWS rows."""
        n = len(timestamps_ns)
        if n < COLUMN_MIN_ROWS:
            of = [0] * n if part_of_row is None else part_of_row
            return [
                CanonicalVoteEncoder.vote_from_parts(*parts[p], t)
                for p, t in zip(of, timestamps_ns)
            ], n
        try:
            ts = np.array(timestamps_ns, dtype=np.int64)
        except OverflowError:
            ts = np.array(
                [
                    t if 0 <= t < _COLUMN_NS_LIMIT else -1
                    for t in timestamps_ns
                ],
                dtype=np.int64,
            )
        part = (
            np.zeros(n, dtype=np.intp)
            if part_of_row is None
            else np.asarray(part_of_row, dtype=np.intp)
        )
        # parts of one (prefix, suffix) length share a group's layout;
        # within it a row's prefix and suffix are gathered by part
        shape_of: dict = {}  # (len prefix, len suffix) -> (id, parts)
        part_shape, part_slot = [], []
        for prefix, suffix in parts:
            sid, members = shape_of.setdefault(
                (len(prefix), len(suffix)), (len(shape_of), [])
            )
            part_shape.append(sid)
            part_slot.append(len(members))
            members.append((prefix, suffix))
        shapes = [(lens, members) for lens, (_, members) in shape_of.items()]

        ok = ts >= 0
        seconds, nanos = np.divmod(np.where(ok, ts, 0), _NS_PER_S)
        s_len, s_bytes = _varint_columns(seconds)
        n_len, n_bytes = _varint_columns(nanos)
        key = (np.asarray(part_shape, dtype=np.int64)[part] * 6 + s_len) * 6
        key = np.where(ok, key + n_len, -1)
        slot = np.asarray(part_slot, dtype=np.intp)[part]

        order = np.argsort(key, kind="stable")
        sorted_key = key[order]
        cuts = np.flatnonzero(sorted_key[1:] != sorted_key[:-1]) + 1
        built: list[bytes] = []
        fallback = 0
        for lo, hi in zip([0, *cuts.tolist()], [*cuts.tolist(), n]):
            rows = order[lo:hi]
            k = int(sorted_key[lo])
            if k < 0:
                fallback += hi - lo
                built.extend(
                    CanonicalVoteEncoder.vote_from_parts(
                        *parts[part[r]], timestamps_ns[r]
                    )
                    for r in rows.tolist()
                )
                continue
            (plen, slen), members = shapes[k // 36]
            built.extend(
                _vote_group(
                    members, slot[rows], plen, slen,
                    s_bytes[rows, : k // 6 % 6], n_bytes[rows, : k % 6],
                )
            )
        if not cuts.size:  # one group: already in row order
            return built, fallback
        back = np.empty(n, dtype=np.intp)
        back[order] = np.arange(n)
        return list(map(built.__getitem__, back.tolist())), fallback

    @staticmethod
    def vote(
        msg_type: int,
        height: int,
        round_: int,
        block_id_bytes: bytes,
        timestamp_ns: int,
        chain_id: str,
    ) -> bytes:
        prefix, suffix = CanonicalVoteEncoder.vote_parts(
            msg_type, height, round_, block_id_bytes, chain_id
        )
        return CanonicalVoteEncoder.vote_from_parts(
            prefix, suffix, timestamp_ns
        )

    @staticmethod
    def proposal(
        height: int,
        round_: int,
        pol_round: int,
        block_id_bytes: bytes,
        timestamp_ns: int,
        chain_id: str,
    ) -> bytes:
        body = b"".join(
            [
                pio.field_varint(1, PROPOSAL_TYPE),
                pio.field_sfixed64(2, height),
                pio.field_sfixed64(3, round_),
                pio.field_sfixed64(4, pol_round),
                (
                    pio.field_message(5, block_id_bytes)
                    if block_id_bytes
                    else b""
                ),
                pio.field_message(6, encode_timestamp(timestamp_ns)),
                pio.field_bytes(7, chain_id.encode()),
            ]
        )
        return pio.marshal_delimited(body)


def vote_sign_bytes(chain_id: str, vote) -> bytes:
    """The message the TPU verifier checks per vote
    (reference types/vote.go:95 VoteSignBytes)."""
    bid = vote.block_id
    return CanonicalVoteEncoder.vote(
        vote.type,
        vote.height,
        vote.round,
        canonical_block_id(
            bid.hash, bid.part_set_header.total, bid.part_set_header.hash
        ),
        vote.timestamp_ns,
        chain_id,
    )


def proposal_sign_bytes(chain_id: str, proposal) -> bytes:
    bid = proposal.block_id
    return CanonicalVoteEncoder.proposal(
        proposal.height,
        proposal.round,
        proposal.pol_round,
        canonical_block_id(
            bid.hash, bid.part_set_header.total, bid.part_set_header.hash
        ),
        proposal.timestamp_ns,
        chain_id,
    )
