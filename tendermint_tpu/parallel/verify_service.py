"""Verify-as-a-service: one device-owning scheduler process serving a
whole committee over Unix-domain-socket IPC.

PR 9's live nets above ~32 validators are event-loop-bound and ran with
stubbed signature verification — a single-process harness cannot absorb
100 nodes' device verifies, so the committee-crypto cost model has never
been measured end-to-end on this stack. This module lifts the PR 3
cross-subsystem coalescing design one level, to cross-PROCESS:

- **`VerifyServiceServer`**: a standalone process
  (`python -m tendermint_tpu verify-service`) owns the `VerifyScheduler`
  — and with it the `BatchVerifier`, the device mesh, the shape
  registry, the DispatchLedger and the prewarm ladder — and serves a
  length-prefixed binary protocol over a UDS. Submissions from ANY
  connected client land in the same class queues, so rounds coalesce
  across processes: one padded device dispatch per round for the whole
  rack. Per-client FIFO holds because each connection's frames decode
  and enqueue in read order and the scheduler preserves per-class FIFO.
  The server also serves its own `/metrics` + `/dump_dispatch_ledger`
  over a TCP stats port (reusing libs/metrics + obs/ledger), so the
  PR 12 multi-tenant device bill now has real tenants: per-client
  submission/row counts ride the dump next to the per-class ledger.

- **`RemoteVerifyScheduler`**: the client, with the exact
  `submit`/`submit_fn`/`submit_sync`/`submit_fn_sync`/`classed` surface
  of the in-proc scheduler, so `set_default_scheduler(remote)` captures
  every subsystem's verify path unchanged. Connection retry with capped
  exponential backoff; when the socket dies MID-FLIGHT every pending
  submission degrades to the local in-proc verifier on this process:
  never hang, never silently drop a verdict. That verifier is a CPU
  verifier — the chip belongs to the service, so whoever builds this
  client pins the process to the CPU platform first
  (libs/device.pin_cpu; node assembly does). Each degrade lands a
  structured `verify_service.degrade`
  tracer event + `tm_verify_remote_degrades_total`; submit→verdict
  round trips feed cumulative `ipc_stats()` that the health plane's
  `ipc_round_trip` detector (obs/health.py) watches for drift.

- **fn lanes ride the same wire**: callers whose private-engine rounds
  are pure functions of wire-able items submit them by NAME to engines
  registered server-side — `bls_agg` (grouped same-message BLS
  aggregate verification over raw public-key bytes; the client resolves
  tm→BLS keys since the registry is client-side state) and
  `secp_recover` (sequencer ECDSA: eth-address recovery over
  (hash, sig) pairs; the membership check stays client-side). Closures
  that cannot cross a process boundary run locally, exactly as before.

Wire format (all integers big-endian):

    frame    := u32 length | payload            (length = len(payload))
    payload  := u8 type | u64 request_id | body
    SUBMIT(10)      body := str8 klass | u32 n | key_types | column pubkeys
                       | column msgs | column sigs | [ctx]
    key_types       := u8 k | k * str8 name | [n * u8 code]
                    (the distinct names, sorted; the codes, one a row,
                    an index into the names, only where k > 1; k = 0
                    only where n = 0; an empty name reads as ed25519)
    column          := u8 0 | u32 blob_len | u32 width | blob
                     | u8 1 | u32 blob_len | n * u32 length | blob
                    (the rows' bytes end to end. Form 0 where every row
                    is `width` bytes long, width > 0 unless n = 0, and
                    blob_len = n * width; form 1 otherwise, blob_len =
                    the sum of the lengths. The encoder reads the form
                    off the rows it is given; a decoder checks every
                    size against the frame before it builds a row)
    SUBMIT_LEGACY(1) body := str8 klass | u32 n | n * sigitem | [ctx]
    sigitem         := str8 key_type | bytes16 pubkey | bytes32 msg
                       | bytes16 sig
                    (decode-only: the per-item frame of clients older
                    than SUBMIT(10). The service decodes and serves it
                    as before; no client of this tree sends it)
    VERDICTS(2)     body := u32 n | ceil(n/8) bitmap (little-bit-order)
    SUBMIT_FN(3)    body := str8 klass | str8 engine | u32 n | n * item
                    | [ctx]
    item            := u8 nparts | nparts * bytes32
    ctx             := u64 height | u32 round | str8 origin | [stamps]
                    (optional trailer: clients stamp the consensus
                    height in progress + their identity so the service
                    records queue/dispatch/device sub-spans under the
                    submitter's span context; a decoder that stops at
                    the last item ignores it, so old servers accept new
                    clients and vice versa)
    stamps          := u64 t_submit_ns | u64 t_encoded_ns | [links]
                    (optional within the trailer: the client's clock at
                    the entry of its submit and once the items were
                    encoded; see SHARED_CLOCK)
    links           := u64 t_gather_ns | u64 prev_req | u64 t_prev_done_ns
                    (optional after the stamps, on the same clock: when
                    the caller's gather of these items began, 0 where
                    the submission came from no gather; and the request
                    this client finished last, with the instant its
                    reader had decoded that reply, 0 and 0 before the
                    first. An older client's 16-byte stamps end before
                    them and decode as before)
    FN_RESULTS(4)   body := u32 n | n * (u8 tag | [u32 len | bytes])
                    tag: 0=False 1=True 2=None 3=bytes
    PING(5)/PONG(6) body := opaque (echoed verbatim)
    STATS(7)        body := empty
    STATS_RESULT(8) body := u32 len | JSON
    ERROR(9)        body := u32 len | utf-8 message

`str8` = u8 length + bytes; `bytes16`/`bytes32` = u16/u32 length +
bytes. Frames are capped at MAX_FRAME; an oversized or undecodable
frame errors the connection (the client degrades and reconnects).

Compatibility, both ways. An older client's SUBMIT_LEGACY(1) frames are
served by this service like any other submission (the dump counts them
under `submit_frames.v1`). A client of this tree sends SUBMIT(10) and
nothing else; a service older than that frame answers it with its
`unknown frame type 10` ERROR frame, and the client verifies that
submission locally, as on any ERROR frame.
"""

from __future__ import annotations

import asyncio
import gc
import itertools
import json
import os
import struct
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

import numpy as np

from ..crypto.batch_verifier import SigItem, default_verifier
from ..crypto.shape_registry import default_shape_registry
from ..libs.device import device_info
from ..libs.jax_cache import compile_log, configure_compile_cache
from ..libs.log import Logger, nop_logger
from ..libs.metrics import (
    Registry,
    RemoteSchedulerMetrics,
    default_metrics,
    default_registry,
)
from ..obs import default_tracer
from ..obs.ledger import default_ledger
from ..obs.profiler import ProfileCapture, ProfilerUnavailable
from .scheduler import VerifyScheduler, _ClassedVerifier

MSG_SUBMIT_LEGACY = 1  # decode-only: the per-item frame of older clients
MSG_VERDICTS = 2
MSG_SUBMIT_FN = 3
MSG_FN_RESULTS = 4
MSG_PING = 5
MSG_PONG = 6
MSG_STATS = 7
MSG_STATS_RESULT = 8
MSG_ERROR = 9
MSG_SUBMIT = 10  # columnar: the one submit frame a client sends

# one frame bounds one submission; 64 MiB holds ~380k vote-sized items,
# far past max_batch — anything bigger is a protocol violation, not load
MAX_FRAME = 64 * 1024 * 1024

# structured degrade event name (tracer ring / dump_traces)
DEGRADE_EVENT = "verify_service.degrade"

_U8 = struct.Struct(">B")
_U16 = struct.Struct(">H")
_U32 = struct.Struct(">I")
_U64 = struct.Struct(">Q")
_HDR = struct.Struct(">BQ")  # type, request_id
_STAMPS = struct.Struct(">QQ")  # t_submit_ns, t_encoded_ns
_LINKS = struct.Struct(">QQQ")  # t_gather_ns, prev_req, t_prev_done_ns
_COL = struct.Struct(">BI")  # a column's form, blob_len

# the two forms of a byte column: one width for every row, or n lengths
COL_WIDTH = 0
COL_LENGTHS = 1


# Frame decode violations (cap, truncation, unknown tag) share the
# engine-item violation class — both are protocol errors, and the
# engines live in parallel/engines.py so the in-proc scheduler resolves
# the same table
from .engines import WireError  # noqa: F401  (re-export, wire contract)


# --- encoding helpers -------------------------------------------------------


def _put_str8(out: list, s: str) -> None:
    b = s.encode()
    if len(b) > 255:
        raise WireError(f"str8 too long: {len(b)}")
    out.append(_U8.pack(len(b)))
    out.append(b)


def _put_bytes32(out: list, b: bytes) -> None:
    out.append(_U32.pack(len(b)))
    out.append(b)


class _Cursor:
    __slots__ = ("buf", "off")

    def __init__(self, buf: bytes):
        self.buf = buf
        self.off = 0

    def take(self, n: int) -> bytes:
        if self.off + n > len(self.buf):
            raise WireError("truncated frame")
        b = self.buf[self.off : self.off + n]
        self.off += n
        return b

    def u8(self) -> int:
        return _U8.unpack(self.take(1))[0]

    def u16(self) -> int:
        return _U16.unpack(self.take(2))[0]

    def u32(self) -> int:
        return _U32.unpack(self.take(4))[0]

    def str8(self) -> str:
        try:
            return self.take(self.u8()).decode()
        except UnicodeDecodeError as e:
            # a corrupt name field is a protocol violation like any
            # other malformed frame — it must ride the WireError
            # contract, not kill the handler task unlogged
            raise WireError(f"invalid str8: {e}") from None

    def bytes16(self) -> bytes:
        return self.take(self.u16())

    def bytes32(self) -> bytes:
        return self.take(self.u32())


# The stamps' clock. Client and service share a host by construction (a
# unix socket), and on Linux `time.perf_counter` is the system-wide
# CLOCK_MONOTONIC: one reading means the same instant in both processes,
# and it is the clock obs/tracer.py already records on. Where
# perf_counter is another clock the client stamps nothing and the
# service reads no stamp.
SHARED_CLOCK = "CLOCK_MONOTONIC" in time.get_clock_info(
    "perf_counter"
).implementation

# a stamp older than this (or from the future) is another clock's
STAMP_MAX_AGE_S = 60.0

# GET /profile_start?seconds=N on the stats port: a session closes by
# itself after N seconds
PROFILE_DEFAULT_S = 5.0
PROFILE_MAX_S = 30.0

# records of the standalone service's ring: a request leaves about 19
# (its gather, its way in and out, queue, prep, lookup, device), and a
# collection one, so this holds a 30-second window of 56 requests a
# second more than twice
SERVICE_RING_SIZE = 65536

# a connection's newest replies whose write instant the service keeps
# for `verify.wire_out`, until the client's next frame names one
REPLIES_KEPT = 64


def _put_trace_ctx(out: list, ctx) -> None:
    """Optional trace-context trailer: (height, round, origin) and,
    where the client read one, its `t_submit_ns`, then, where given,
    the links (`t_gather_ns`, `prev_req`, `t_prev_done_ns`). The second
    stamp, `t_encoded_ns`, is read here: the trailer stands at the END
    of the frame so that it is written once the items are encoded."""
    if ctx is None:
        return
    height, round_, origin, *stamp = ctx
    out.append(_U64.pack(max(0, int(height))))
    out.append(_U32.pack(max(0, int(round_))))
    _put_str8(out, str(origin))
    if stamp:
        out.append(_STAMPS.pack(stamp[0], time.perf_counter_ns()))
        if len(stamp) > 1:
            out.append(_LINKS.pack(*stamp[1:]))


def decode_trace_ctx(cur: _Cursor, req_id: int):
    """The trailer, if the frame carries one; the req_id joins the
    client's round-trip span to the service's sub-spans. Returns
    (height, round, origin, req_id) or None."""
    if cur.off >= len(cur.buf):
        return None
    height = _U64.unpack(cur.take(8))[0]
    round_ = cur.u32()
    origin = cur.str8()
    return (height, round_, origin, req_id)


def decode_trace_stamps(cur: _Cursor):
    """(t_submit_s, t_encoded_s, t_gather_s, prev_req, t_prev_done_s)
    after the trailer's three fields, times in seconds of the shared
    clock: `t_gather_s` None where the submission came from no gather,
    `prev_req` 0 and `t_prev_done_s` None where the client had finished
    no request, and both so for an older client's 16-byte stamps. None:
    a frame with no trailer or with the three-field trailer of an older
    client carries no stamps."""
    left = len(cur.buf) - cur.off
    if not SHARED_CLOCK or left < _STAMPS.size:
        return None
    t_submit, t_encoded = _STAMPS.unpack(cur.take(_STAMPS.size))
    t_gather = prev_req = t_prev_done = 0
    if left >= _STAMPS.size + _LINKS.size:
        t_gather, prev_req, t_prev_done = _LINKS.unpack(
            cur.take(_LINKS.size)
        )
    return (
        t_submit * 1e-9,
        t_encoded * 1e-9,
        t_gather * 1e-9 if t_gather else None,
        prev_req,
        t_prev_done * 1e-9 if prev_req else None,
    )


def _put_key_types(out: list, key_types: list) -> None:
    names = sorted(set(key_types))
    if len(names) > 255:
        raise WireError(f"{len(names)} key types in one submission")
    out.append(_U8.pack(len(names)))
    for name in names:
        _put_str8(out, name)
    if len(names) > 1:
        code = {name: i for i, name in enumerate(names)}
        out.append(bytes(map(code.__getitem__, key_types)))


def _put_column(out: list, rows: list) -> None:
    """One byte column: a single width where every row has that length,
    n lengths otherwise, then the rows themselves: the frame's one
    `b"".join` lays them end to end, so no column is copied twice."""
    n = len(rows)
    lens = np.fromiter(map(len, rows), dtype=np.uint32, count=n)
    blob_len = int(lens.sum(dtype=np.uint64))
    if blob_len > MAX_FRAME:
        raise WireError(f"column of {blob_len} bytes exceeds the frame cap")
    width = int(lens[0]) if n else 0
    if not n or (width and (lens == width).all()):
        out.append(_COL.pack(COL_WIDTH, blob_len))
        out.append(_U32.pack(width))
    else:
        out.append(_COL.pack(COL_LENGTHS, blob_len))
        out.append(lens.astype(">u4").tobytes())
    out.extend(rows)


def encode_submit(
    req_id: int, items: list[SigItem], klass: str, ctx=None
) -> bytes:
    """The columnar submit frame: a constant number of steps a column
    (one pass over the items' attribute, its lengths through numpy, one
    `extend`), none an item."""
    out = [_HDR.pack(MSG_SUBMIT, req_id)]
    _put_str8(out, klass)
    out.append(_U32.pack(len(items)))
    _put_key_types(out, [it.key_type for it in items])
    _put_column(out, [it.pubkey for it in items])
    _put_column(out, [it.msg for it in items])
    _put_column(out, [it.sig for it in items])
    _put_trace_ctx(out, ctx)
    return b"".join(out)


def _take_key_types(cur: _Cursor, n: int):
    """An iterable of n key-type names."""
    k = cur.u8()
    names = [cur.str8() or "ed25519" for _ in range(k)]
    if k == 0:
        if n:
            raise WireError(f"no key type for {n} rows")
        return ()
    if k == 1:
        return itertools.repeat(names[0], n)
    codes = cur.take(n)
    if n and max(codes) >= k:
        raise WireError(f"key-type code past the {k} names")
    return map(names.__getitem__, codes)


def _take_column(cur: _Cursor, n: int):
    """Step over one byte column, checking what it says of its size
    against the frame: (offset of the blob, width, lengths), of the
    last two the one its form carries. Builds nothing of n's size that
    the frame does not hold."""
    form, blob_len = _COL.unpack(cur.take(_COL.size))
    width = lens = None
    if form == COL_WIDTH:
        width = cur.u32()
        if n * width != blob_len or (n and not width):
            raise WireError(
                f"column of {n} rows by {width} bytes in a blob of {blob_len}"
            )
    elif form == COL_LENGTHS:
        lens = np.frombuffer(cur.take(4 * n), dtype=">u4")
        total = int(lens.sum(dtype=np.uint64))
        if total != blob_len:
            raise WireError(
                f"column lengths sum to {total} in a blob of {blob_len}"
            )
    else:
        raise WireError(f"unknown column form {form}")
    start = cur.off
    if start + blob_len > len(cur.buf):
        raise WireError("truncated frame")
    cur.off = start + blob_len
    return start, width, lens


def _column_rows(buf: bytes, n: int, start: int, width, lens) -> list:
    """The n rows of a column that `_take_column` has checked, each a
    `bytes` of its own."""
    if n == 0:
        return []
    if width is not None:
        # one C-level pass: an array of n width-byte voids, as bytes
        return np.frombuffer(
            buf, dtype=np.dtype((np.void, width)), count=n, offset=start
        ).tolist()
    ends = (np.cumsum(lens, dtype=np.int64) + start).tolist()
    return [buf[a:b] for a, b in zip([start] + ends, ends)]


def decode_submit(cur: _Cursor) -> tuple[list[SigItem], str, bool]:
    """(items, klass, uniform): the list that was encoded, row for row;
    `uniform` says that every byte column came in its single-width
    form. Every size the frame states is checked against the frame
    before a row is built."""
    klass = cur.str8()
    n = cur.u32()
    key_types = _take_key_types(cur, n)
    cols = [_take_column(cur, n) for _ in range(3)]
    pubkeys, msgs, sigs = (_column_rows(cur.buf, n, *col) for col in cols)
    items = list(map(SigItem, pubkeys, msgs, sigs, key_types))
    return items, klass, all(width is not None for _, width, _ in cols)


def decode_submit_legacy(cur: _Cursor) -> tuple[list[SigItem], str]:
    """The per-item SUBMIT_LEGACY(1) frame of older clients."""
    klass = cur.str8()
    n = cur.u32()
    items = []
    for _ in range(n):
        key_type = cur.str8() or "ed25519"
        pubkey = cur.bytes16()
        msg = cur.bytes32()
        sig = cur.bytes16()
        items.append(SigItem(pubkey, msg, sig, key_type))
    return items, klass


def encode_verdicts(req_id: int, verdicts: np.ndarray) -> bytes:
    arr = np.asarray(verdicts, dtype=bool)
    bitmap = np.packbits(arr.astype(np.uint8), bitorder="little").tobytes()
    return b"".join(
        (_HDR.pack(MSG_VERDICTS, req_id), _U32.pack(arr.size), bitmap)
    )


def decode_verdicts(cur: _Cursor) -> np.ndarray:
    n = cur.u32()
    bitmap = cur.take((n + 7) // 8)
    if n == 0:
        return np.zeros(0, dtype=bool)
    return (
        np.unpackbits(
            np.frombuffer(bitmap, dtype=np.uint8),
            count=n,
            bitorder="little",
        ).astype(bool)
    )


def encode_submit_fn(
    req_id: int, engine: str, items: list[tuple], klass: str, ctx=None
) -> bytes:
    out = [_HDR.pack(MSG_SUBMIT_FN, req_id)]
    _put_str8(out, klass)
    _put_str8(out, engine)
    out.append(_U32.pack(len(items)))
    for parts in items:
        if len(parts) > 255:
            raise WireError("fn item has too many parts")
        out.append(_U8.pack(len(parts)))
        for p in parts:
            _put_bytes32(out, bytes(p))
    _put_trace_ctx(out, ctx)
    return b"".join(out)


def decode_submit_fn(cur: _Cursor) -> tuple[str, list[tuple], str]:
    klass = cur.str8()
    engine = cur.str8()
    n = cur.u32()
    items = [
        tuple(cur.bytes32() for _ in range(cur.u8())) for _ in range(n)
    ]
    return engine, items, klass


def encode_fn_results(req_id: int, results: list) -> bytes:
    out = [_HDR.pack(MSG_FN_RESULTS, req_id), _U32.pack(len(results))]
    for r in results:
        if r is None:
            out.append(_U8.pack(2))
        elif isinstance(r, (bytes, bytearray)):
            out.append(_U8.pack(3))
            _put_bytes32(out, bytes(r))
        else:
            out.append(_U8.pack(1 if r else 0))
    return b"".join(out)


def decode_fn_results(cur: _Cursor) -> list:
    n = cur.u32()
    out: list = []
    for _ in range(n):
        tag = cur.u8()
        if tag == 0:
            out.append(False)
        elif tag == 1:
            out.append(True)
        elif tag == 2:
            out.append(None)
        elif tag == 3:
            out.append(cur.bytes32())
        else:
            raise WireError(f"unknown fn-result tag {tag}")
    return out


def encode_error(req_id: int, message: str) -> bytes:
    b = message.encode()[:4096]
    return b"".join((_HDR.pack(MSG_ERROR, req_id), _U32.pack(len(b)), b))


async def read_frame(reader: asyncio.StreamReader) -> Optional[bytes]:
    """One length-prefixed frame, or None on clean EOF."""
    try:
        hdr = await reader.readexactly(4)
    except (asyncio.IncompleteReadError, ConnectionError):
        return None
    (length,) = _U32.unpack(hdr)
    if length > MAX_FRAME:
        raise WireError(f"frame of {length} bytes exceeds cap")
    try:
        return await reader.readexactly(length)
    except (asyncio.IncompleteReadError, ConnectionError):
        return None


def write_frame(writer: asyncio.StreamWriter, payload: bytes) -> None:
    writer.write(_U32.pack(len(payload)) + payload)


# --- server-side fn engines -------------------------------------------------
# The table lives in parallel/engines.py (shared with the in-proc
# scheduler's submit_wire_fn); re-exported names keep existing callers
# (tools/verify_service_bench.py) working.

from .engines import (  # noqa: E402,F401
    BUILTIN_ENGINES,
    _engine_bls_agg,
    _engine_secp_recover,
)


# --- the server -------------------------------------------------------------


class _WayIn:
    """What `_handle_conn` knows of a submission's way in, taken once
    the trailer is decoded: the frame's size, when the whole frame was
    held, when its decode ended, the client's stamps if it sent any,
    what `verify.frame_decode` says of the frame it decoded (for a
    signature submission `frame`, `cols` or `v1`, and `uniform`, every
    byte column in its single-width form), and the connection's
    `replies`: {req: (t_ready, ctx, fields)} of the replies it began
    writing, from which `prev` is taken, the reply the client names as
    the last it finished."""

    __slots__ = (
        "nbytes", "t_frame", "t_decoded", "stamps", "decoded", "replies",
        "prev",
    )

    def __init__(self, cur: _Cursor, t_frame: float, replies: dict,
                 **decoded):
        self.nbytes = len(cur.buf)
        self.t_frame = t_frame
        self.stamps = decode_trace_stamps(cur)
        self.t_decoded = time.perf_counter()
        self.decoded = decoded
        self.replies = replies
        self.prev = (
            replies.pop(self.stamps[3], None)
            if replies and self.stamps is not None else None
        )


class VerifyServiceServer:
    """Owns the scheduler/device plane and serves the UDS protocol.

    Lifecycle: construct, `await start()` on the serving loop,
    `await stop()`. `stats_port` > 0 additionally serves GET /metrics
    (the process registry, text exposition), GET /dump_dispatch_ledger
    (the same JSON shape as the node RPC route, plus per-client tenant
    rows) and GET /dump_traces (the service flight ring in the node
    dump_traces shape, mergeable by obs/cluster.py) over TCP —
    `tools/device_report.py` reads those dumps directly."""

    def __init__(
        self,
        path: str,
        scheduler: Optional[VerifyScheduler] = None,
        verifier=None,
        max_batch: int = 16384,
        logger: Optional[Logger] = None,
        stats_port: Optional[int] = None,
        stats_host: str = "127.0.0.1",
        registry: Optional[Registry] = None,
        engines: Optional[dict] = None,
        tracer=None,
    ):
        self.path = path
        self.logger = logger or nop_logger()
        # the service's own flight ring: traced client submissions land
        # their queue/dispatch/device sub-spans here, and GET
        # /dump_traces on the stats port ships it in the dump_traces
        # shape so obs/cluster.py merges it next to validator dumps
        # (is-None check — an empty Tracer is falsy via __len__)
        self.tracer = tracer
        self.scheduler = scheduler or VerifyScheduler(
            verifier=verifier, max_batch=max_batch, logger=self.logger,
            tracer=tracer,
        )
        self.registry = registry or default_registry()
        self.stats_port = stats_port
        self.stats_host = stats_host
        self.engines = dict(BUILTIN_ENGINES)
        if engines:
            self.engines.update(engines)
        self._server: Optional[asyncio.AbstractServer] = None
        self._stats_server: Optional[asyncio.AbstractServer] = None
        self._conn_tasks: set[asyncio.Task] = set()
        self._next_client = 0
        # tenant accounting: client_id -> {submissions, rows, ...}
        # (per CONNECTION; a closed client's spend stays in the bill —
        # a tenant's work doesn't vanish on disconnect). BOUNDED: a
        # closed connection that never submitted is dropped outright
        # (a flapping client at the 2 s backoff cap would otherwise
        # add ~43k dead entries/day), and past MAX_CLIENT_STATS the
        # oldest CLOSED entries fold into one "_closed" aggregate row
        # so the table and every STATS/dump response stay bounded
        self.client_stats: dict[str, dict] = {}
        self.max_client_stats = 1024
        # rounds answered with an ERROR frame: clients absorb those by
        # verifying locally, so this count is where a failing device
        # shows
        self.error_frames = 0
        # signature submissions by the frame that carried them: `v1`
        # counts the clients that still send SUBMIT_LEGACY
        self.submit_frames = {"cols": 0, "v1": 0}
        # GET /profile_start | /profile_stop on the stats port: only the
        # process that holds the chip can trace it. One thread starts
        # and stops every session, never the event loop (exporting a
        # trace takes seconds to tens of seconds)
        self.profiler = ProfileCapture(
            path + ".profiles", logger=self.logger
        )
        self._profile_pool = ThreadPoolExecutor(
            1, thread_name_prefix="verify-profile"
        )
        self._profile_timer: Optional[asyncio.Task] = None
        self._last_profile: Optional[dict] = None
        self._loop_thread = 0
        # with the ring armed, one `runtime.gc` span a collection
        # (installed by start, removed by stop; never with it off)
        self._gc_hook = None
        self._gc_t0: Optional[float] = None

    async def start(self) -> None:
        self._loop_thread = threading.get_ident()
        if self._trace().enabled and self._gc_hook is None:
            self._gc_hook = self._collector_span
            gc.callbacks.append(self._gc_hook)
        if not self.scheduler.running:
            await self.scheduler.start()
        # a stale socket file from a crashed predecessor refuses bind
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass
        self._server = await asyncio.start_unix_server(
            self._handle_conn, path=self.path
        )
        # stats_port None = no HTTP surface; 0 = ephemeral (read the
        # bound port back from .stats_port)
        if self.stats_port is not None:
            self._stats_server = await asyncio.start_server(
                self._handle_stats_http, self.stats_host, self.stats_port
            )
            self.stats_port = (
                self._stats_server.sockets[0].getsockname()[1]
            )
        self.logger.info(
            "verify service listening", socket=self.path,
            stats_port=self.stats_port or None,
        )

    async def stop(self) -> None:
        servers = [
            s for s in (self._server, self._stats_server) if s is not None
        ]
        self._server = self._stats_server = None
        for srv in servers:
            srv.close()
        # the connection tasks end before wait_closed(): since Python
        # 3.12 it waits for every open connection, so awaiting it first
        # never returns while a client is attached, and the client
        # never learns that the service is gone
        tasks = list(self._conn_tasks)
        for t in tasks:
            t.cancel()
        for t in tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        for srv in servers:
            await srv.wait_closed()
        if self.profiler.active:
            await self._profile_stop()
        self._profile_pool.shutdown(wait=False)
        await self.scheduler.stop()
        hook, self._gc_hook = self._gc_hook, None
        if hook is not None:
            gc.callbacks.remove(hook)
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass

    def _collector_span(self, phase: str, info: dict) -> None:
        """`gc.callbacks` hook: one `runtime.gc` span a collection, in
        whichever thread it ran (`generation`, `collected`,
        `uncollectable`). Recorded as the collection ends; a collection
        inside a span recorded afterwards (`verify.frame_decode`) is
        placed by time, as the shorter span inside it."""
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is not None:
            self._trace().add_span(
                "runtime.gc", t0, time.perf_counter() - t0,
                generation=info["generation"],
                collected=info["collected"],
                uncollectable=info["uncollectable"],
            )

    # --- stats/dump surface ------------------------------------------------

    def dump(self, entries: int = 128) -> dict:
        """The dump_dispatch_ledger shape + the tenant table. The
        `service` block says which device this process resolved (and
        its peak memory where the backend reports it) and what it
        compiled — "the node committed blocks" proves nothing about
        the chip, this block does."""
        ledger = self.scheduler.ledger
        return {
            "enabled": True,
            "service": {
                "socket": self.path,
                "pid": os.getpid(),
                **device_info(),
                "compile": compile_log().snapshot(),
                "error_frames": self.error_frames,
                "submit_frames": dict(self.submit_frames),
                "table_store": self._table_store(),
            },
            "summary": ledger.summary(),
            "entries": ledger.entries(limit=entries) if entries > 0 else [],
            "shape_registry": default_shape_registry().snapshot(),
            "per_client": {
                k: dict(v) for k, v in sorted(self.client_stats.items())
            },
        }

    def _table_store(self) -> dict:
        """The verifier's key-table stores, per tier: rows_allocated,
        keys_resident, bytes, and the cumulative evictions and
        fallback_rounds (rounds with more distinct keys than the store
        holds, answered by the generic program). Empty for a verifier
        that keeps no such store."""
        stats = getattr(self.scheduler.verifier, "table_store_stats", None)
        return stats() if stats is not None else {}

    def _trace(self):
        from ..obs import default_tracer as _dt

        return self.tracer if self.tracer is not None else _dt()

    def trace_dump(self) -> dict:
        """The service ring in the node `dump_traces` response shape, so
        obs.cluster.normalize_dump accepts it unchanged. No peer_clock:
        the service sits outside the p2p NTP graph, which routes its
        merge through the raw-wall-anchor fallback by design."""
        tracer = self._trace()
        return {
            "enabled": tracer.enabled,
            "epoch_wall_ns": tracer.epoch_wall_ns,
            "node_id": f"verify-service-{os.getpid()}",
            "moniker": "verify-service",
            "peer_clock": {},
            "records": [r.to_json() for r in tracer.records()],
        }

    # --- device profiler (GET /profile_start, /profile_stop) -------------

    async def _profile_start(self, query: dict) -> tuple[int, dict]:
        """Open a profiler session in this process (device and XLA host
        events, the Python tracer off; the event loop's stack sampled
        beside it) that stops by itself after `seconds` (default
        PROFILE_DEFAULT_S, at most PROFILE_MAX_S). One at a time."""
        try:
            seconds = float(query.get("seconds", PROFILE_DEFAULT_S))
        except ValueError:
            return 400, {"error": "seconds is not a number"}
        seconds = min(PROFILE_MAX_S, max(0.0, seconds))
        loop = asyncio.get_running_loop()
        try:
            started = await loop.run_in_executor(
                self._profile_pool,
                lambda: self.profiler.start(
                    label=query.get("label", ""),
                    thread_id=self._loop_thread,
                    python_tracer=False,
                ),
            )
        except ProfilerUnavailable as e:
            return 409, {"error": str(e)}
        self._profile_timer = loop.create_task(self._stop_after(seconds))
        return 200, {"started": True, "seconds": seconds, **started}

    async def _stop_after(self, seconds: float) -> None:
        await asyncio.sleep(seconds)
        self._profile_timer = None  # this task must not cancel itself
        await self._profile_stop()

    async def _profile_stop(self) -> tuple[int, dict]:
        """Close the session and write its trace; `stop_s` is what that
        took. With none open: 409, beside the last session's record."""
        timer, self._profile_timer = self._profile_timer, None
        if timer is not None:
            timer.cancel()
        t0 = time.perf_counter()
        try:
            session = await asyncio.get_running_loop().run_in_executor(
                self._profile_pool, self.profiler.stop
            )
        except ProfilerUnavailable as e:
            return 409, {"error": str(e), "last": self._last_profile}
        session["stop_s"] = round(time.perf_counter() - t0, 3)
        self._last_profile = session
        self.logger.info(
            "profile session written", dir=session["dir"],
            duration_s=session["duration_s"], stop_s=session["stop_s"],
        )
        return 200, session

    # --- UDS protocol ------------------------------------------------------

    def _prune_client_stats(self) -> None:
        """Fold the oldest closed per-connection rows into "_closed"
        once the table exceeds max_client_stats (insertion order =
        connection order, so iteration finds the oldest first)."""
        agg = self.client_stats.setdefault(
            "_closed",
            {"submissions": 0, "rows": 0, "fn_submissions": 0,
             "fn_items": 0, "clients": 0},
        )
        excess = len(self.client_stats) - self.max_client_stats
        for name in [
            k
            for k, v in self.client_stats.items()
            if v.get("closed") and k != "_closed"
        ][:max(0, excess)]:
            v = self.client_stats.pop(name)
            for key in ("submissions", "rows", "fn_submissions",
                        "fn_items"):
                agg[key] += v[key]
            agg["clients"] += 1

    async def _handle_conn(self, reader, writer) -> None:
        self._next_client += 1
        client = f"client-{self._next_client}"
        stats = self.client_stats[client] = {
            "submissions": 0, "rows": 0, "fn_submissions": 0,
            "fn_items": 0,
        }
        if len(self.client_stats) > self.max_client_stats:
            self._prune_client_stats()
        wlock = asyncio.Lock()
        task = asyncio.current_task()
        self._conn_tasks.add(task)
        pending: set[asyncio.Task] = set()
        replies: dict = {}

        async def send(payload: bytes) -> None:
            async with wlock:
                write_frame(writer, payload)
                await writer.drain()

        def spawn(coro) -> None:
            t = asyncio.get_running_loop().create_task(coro)
            pending.add(t)
            t.add_done_callback(pending.discard)

        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    break
                t_frame = time.perf_counter()
                cur = _Cursor(frame)
                typ, req_id = _HDR.unpack(cur.take(_HDR.size))
                if typ in (MSG_SUBMIT, MSG_SUBMIT_LEGACY):
                    if typ == MSG_SUBMIT:
                        kind = "cols"
                        items, klass, uniform = decode_submit(cur)
                    else:
                        kind, uniform = "v1", False
                        items, klass = decode_submit_legacy(cur)
                    ctx = decode_trace_ctx(cur, req_id)
                    way_in = _WayIn(
                        cur, t_frame, replies, frame=kind, uniform=uniform
                    )
                    self.submit_frames[kind] += 1
                    stats["submissions"] += 1
                    stats["rows"] += len(items)
                    # create_task here, synchronously in read order:
                    # tasks first run in creation order and submit()
                    # enqueues before its first await point, so one
                    # client's submissions keep FIFO within their class
                    spawn(
                        self._do_submit(
                            send, req_id, items, klass, ctx, way_in
                        )
                    )
                elif typ == MSG_SUBMIT_FN:
                    engine, items, klass = decode_submit_fn(cur)
                    ctx = decode_trace_ctx(cur, req_id)
                    way_in = _WayIn(cur, t_frame, replies)
                    stats["fn_submissions"] += 1
                    stats["fn_items"] += len(items)
                    spawn(
                        self._do_submit_fn(
                            send, req_id, engine, items, klass, ctx,
                            way_in,
                        )
                    )
                elif typ == MSG_PING:
                    await send(
                        _HDR.pack(MSG_PONG, req_id)
                        + cur.buf[cur.off :]
                    )
                elif typ == MSG_STATS:
                    body = json.dumps(self.dump()).encode()
                    await send(
                        _HDR.pack(MSG_STATS_RESULT, req_id)
                        + _U32.pack(len(body))
                        + body
                    )
                else:
                    await send(
                        encode_error(req_id, f"unknown frame type {typ}")
                    )
        except (WireError, ConnectionError, OSError) as e:
            self.logger.error(
                "verify-service connection error", client=client,
                err=repr(e),
            )
        finally:
            self._conn_tasks.discard(task)
            for t in pending:
                t.cancel()
            if stats["submissions"] or stats["fn_submissions"]:
                stats["closed"] = True  # spend stays billable
            else:
                # a connection that never submitted owes nothing —
                # dropping it keeps a flapping client from growing
                # the table
                self.client_stats.pop(client, None)
            writer.close()

    def _span(self, name, ctx, t0: float, t1: float, **fields) -> None:
        """One span of a traced submission on the service's ring, under
        the submitter's context. A frame with no trailer has no `req`
        to join its spans by and records none."""
        if ctx is None:
            return
        height, round_, origin, req = ctx
        self._trace().add_span(
            name, t0, t1 - t0,
            height=height, round=round_, origin=origin, req=req, **fields,
        )

    def _way_in_spans(self, ctx, way_in, t_recv: float, **fields) -> None:
        """The submission's way from the client's `submit` to here:
        `verify.ingress` (the client's t_submit -> t_recv) around
        `verify.client_encode`, `verify.wire_in` (encoded -> the whole
        frame held) and `verify.frame_decode`; before it, beside
        `verify.ingress`, `verify.client_gather` (the caller's gather
        began -> t_submit). And the way out of the request the client
        finished last: `verify.wire_out`, from the instant this service
        began writing that reply to the one the client had decoded it,
        under that request's context. Stamps that are not of this
        host's clock (in its future, STAMP_MAX_AGE_S old, or out of
        order) are dropped with the spans that need them; the decode is
        the service's own and stays."""
        if not self._trace().enabled:
            return
        oldest = t_recv - STAMP_MAX_AGE_S
        stamps = way_in.stamps
        if way_in.prev is not None:
            t_ready, prev_ctx, prev_fields = way_in.prev
            t_done = stamps[4]
            if oldest <= t_done and t_ready <= t_done <= way_in.t_frame:
                self._span(
                    "verify.wire_out", prev_ctx, t_ready, t_done,
                    **prev_fields,
                )
        if stamps is not None and not (
            oldest <= stamps[0] <= stamps[1] <= way_in.t_frame
        ):
            stamps = None
        if stamps is not None:
            t_submit, t_encoded, t_gather = stamps[:3]
            if t_gather is not None and oldest <= t_gather <= t_submit:
                self._span(
                    "verify.client_gather", ctx, t_gather, t_submit,
                    **fields,
                )
            self._span("verify.ingress", ctx, t_submit, t_recv, **fields)
            fields["parent"] = "verify.ingress"
            self._span(
                "verify.client_encode", ctx, t_submit, t_encoded, **fields
            )
            self._span(
                "verify.wire_in", ctx, t_encoded, way_in.t_frame, **fields
            )
        self._span(
            "verify.frame_decode", ctx, way_in.t_frame, way_in.t_decoded,
            **fields, **way_in.decoded,
        )

    async def _answer(
        self, send, encode, req_id, result, ctx, t_recv: float, replies,
        **fields
    ) -> None:
        """`verify.service` (t_recv -> the answer is ready; what the
        benchmark's `ipc_overhead` subtracts, so its ends stay where
        they are), then `verify.reply`: the reply frame encoded,
        written and drained. With the ring armed, the instant the
        answer was ready is kept in the connection's `replies` (the
        newest REPLIES_KEPT) for the client's next frame to close its
        `verify.wire_out`."""
        t_ready = time.perf_counter()
        self._span("verify.service", ctx, t_recv, t_ready, **fields)
        payload = encode(req_id, result)
        fields["bytes"] = len(payload)
        if ctx is not None and self._trace().enabled:
            replies[req_id] = (t_ready, ctx, fields)
            if len(replies) > REPLIES_KEPT:
                del replies[next(iter(replies))]
        await self._send_guarded(send, payload)
        self._span(
            "verify.reply", ctx, t_ready, time.perf_counter(), **fields
        )

    async def _do_submit(
        self, send, req_id, items, klass, ctx, way_in
    ):
        t_recv = time.perf_counter()
        fields = {"n": len(items), "klass": klass, "bytes": way_in.nbytes}
        self._way_in_spans(ctx, way_in, t_recv, **fields)
        try:
            verdicts = await self.scheduler.submit(items, klass, ctx=ctx)
        except Exception as e:
            await self._send_error(send, req_id, f"verify failed: {e!r}")
            return
        await self._answer(
            send, encode_verdicts, req_id, verdicts, ctx, t_recv,
            way_in.replies, **fields
        )

    async def _do_submit_fn(
        self, send, req_id, engine, items, klass, ctx, way_in
    ):
        fn = self.engines.get(engine)
        if fn is None:
            await self._send_error(
                send, req_id, f"unknown fn engine {engine!r}"
            )
            return
        t_recv = time.perf_counter()
        fields = {"n": len(items), "klass": klass, "bytes": way_in.nbytes}
        self._way_in_spans(ctx, way_in, t_recv, **fields)
        try:
            results = await self.scheduler.submit_fn(
                items, fn, klass, engine=engine, ctx=ctx
            )
        except Exception as e:
            await self._send_error(
                send, req_id, f"fn engine {engine} failed: {e!r}"
            )
            return
        await self._answer(
            send, encode_fn_results, req_id, results, ctx, t_recv,
            way_in.replies, **fields
        )

    async def _send_error(self, send, req_id: int, message: str) -> None:
        self.error_frames += 1
        await self._send_guarded(send, encode_error(req_id, message))

    async def _send_guarded(self, send, payload: bytes) -> None:
        # the client vanishing mid-response is its problem, not ours —
        # its pending futures degrade locally on its side
        try:
            await send(payload)
        except (ConnectionError, OSError):
            pass

    # --- stats HTTP (GET /metrics + /dump_dispatch_ledger) ----------------

    async def _handle_stats_http(self, reader, writer) -> None:
        try:
            req_line = await reader.readline()
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b"\n", b""):
                    break
            try:
                method, target, _ = (
                    req_line.decode().strip().split(" ", 2)
                )
            except (ValueError, UnicodeDecodeError):
                return
            path, _, query = target.partition("?")
            if method != "GET":
                body, status, ctype = b"method not allowed\n", 405, "text/plain"
            elif path == "/metrics":
                body = self.registry.render().encode()
                status, ctype = 200, "text/plain; version=0.0.4"
            elif path == "/dump_dispatch_ledger":
                body = json.dumps(self.dump()).encode()
                status, ctype = 200, "application/json"
            elif path == "/dump_traces":
                body = json.dumps(self.trace_dump()).encode()
                status, ctype = 200, "application/json"
            elif path in ("/profile_start", "/profile_stop"):
                if path == "/profile_start":
                    status, doc = await self._profile_start(
                        dict(urllib.parse.parse_qsl(query))
                    )
                else:
                    status, doc = await self._profile_stop()
                body, ctype = json.dumps(doc).encode(), "application/json"
            else:
                body, status, ctype = b"not found\n", 404, "text/plain"
            reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                      405: "Method Not Allowed", 409: "Conflict"}[status]
            writer.write(
                f"HTTP/1.1 {status} {reason}\r\n"
                f"Content-Type: {ctype}\r\n"
                f"Content-Length: {len(body)}\r\n"
                "Connection: close\r\n\r\n".encode() + body
            )
            await writer.drain()
        finally:
            writer.close()


# --- the client -------------------------------------------------------------


class _RemoteReq:
    __slots__ = (
        "kind", "items", "klass", "future", "fallback", "t0", "ctx",
    )

    def __init__(self, kind, items, klass, future, fallback, t0, ctx=None):
        self.kind = kind  # "sig" | "fn"
        self.items = items
        self.klass = klass
        self.future = future
        self.fallback = fallback  # zero-arg callable for the local path
        self.t0 = t0
        self.ctx = ctx  # (height, round, origin, req_id) when traced


class RemoteVerifyScheduler:
    """Client half of the split-brain deployment: the VerifyScheduler
    surface (`submit`/`submit_fn`/`submit_sync`/`submit_fn_sync`/
    `classed`) over a UDS connection to a VerifyServiceServer, selected
    by `[scheduler] remote_socket` in node assembly.

    Degradation contract (the PR 1 philosophy — never hang, never
    silently drop): while disconnected, and for every submission
    in flight when the socket dies, work runs on the LOCAL in-proc
    verifier instead; each occurrence lands a structured
    `verify_service.degrade` tracer event and counts in
    `tm_verify_remote_degrades_total`. The connection manager retries
    with capped exponential backoff and re-attaches transparently —
    callers only ever see verdicts. A wedged-but-open service (alive
    socket, no replies) is the `ipc_round_trip` health detector's job:
    this client feeds it cumulative submit→verdict latency via
    `ipc_stats()`.

    fn lanes: `submit_fn(_sync)` runs closures LOCALLY (a process
    boundary cannot ship a closure); `submit_wire_fn(_sync)` ships
    items by engine name to the service (bls_agg, secp_recover) with a
    caller-supplied local fallback."""

    def __init__(
        self,
        path: str,
        verifier=None,
        logger: Optional[Logger] = None,
        metrics: Optional[RemoteSchedulerMetrics] = None,
        tracer=None,
        retry_base: float = 0.05,
        retry_cap: float = 2.0,
        origin: str = "",
    ):
        self.path = path
        self._verifier = verifier
        self.logger = logger or nop_logger()
        self.metrics = metrics or default_metrics(RemoteSchedulerMetrics)
        self.tracer = tracer
        # identity stamped into each submission's wire trace context so
        # the service's queue/device sub-spans name their submitter
        # (node assembly passes the node id; harnesses a worker label)
        self.origin = origin or f"client-{os.getpid()}"
        self.retry_base = retry_base
        self.retry_cap = retry_cap
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._wlock: Optional[asyncio.Lock] = None
        self._manager: Optional[asyncio.Task] = None
        # degrade fallbacks run on a PRIVATE pool, never the shared
        # default executor: the callers waiting on those fallbacks are
        # worker threads that each HOLD a default-executor slot
        # (min(32, cpus+4) = 6 on a 2-core box), so a service death
        # with enough submissions in flight used to park every slot on
        # work that could only run in one of those slots — the net
        # froze at the height the kill landed on (the PR 10
        # submit_sync deadlock class, one level up)
        self._fallback_pool: Optional[ThreadPoolExecutor] = None
        self._running = False
        self._connected = asyncio.Event()
        self._next_id = 0
        self._pending: dict[int, _RemoteReq] = {}
        # cumulative IPC round-trip accounting for the health seam
        # (plain counters so the pull-delta pattern works without
        # metrics objects); guarded by the GIL — single-writer loop
        self._rtt_count = 0
        self._rtt_sum = 0.0
        # (req, CLOCK_MONOTONIC ns) of the reply the read loop decoded
        # last: the next submission's trailer carries it, so that the
        # service can time the reply's way back (`verify.wire_out`)
        self._last_done = (0, 0)
        self._remote_submissions = 0
        self._degrades = 0
        self._reconnects = 0

    # the local fallback verifier, resolved lazily so constructing a
    # RemoteVerifyScheduler never forces a jax device init by itself
    @property
    def verifier(self):
        if self._verifier is None:
            self._verifier = default_verifier()
        return self._verifier

    @property
    def running(self) -> bool:
        return self._running

    @property
    def connected(self) -> bool:
        return self._writer is not None

    # ledger parity with VerifyScheduler (node assembly binds
    # health/fill seams to `.ledger`): remote rounds are booked on the
    # SERVICE's ledger, so the client exposes the process default —
    # local degraded rounds the fallback verifier drives are direct
    # dispatches and show up in the shape registry instead
    @property
    def ledger(self):
        return default_ledger()

    def _trace(self):
        # is-None check (Tracer defines __len__; `or` discards an
        # injected-but-empty ring — the PR 4 falsy-tracer class)
        return default_tracer() if self.tracer is None else self.tracer

    def ipc_stats(self) -> dict:
        """Cumulative client-side IPC counters (health pull seam)."""
        return {
            "rtt_count": self._rtt_count,
            "rtt_sum_s": self._rtt_sum,
            "remote_submissions": self._remote_submissions,
            "degrades": self._degrades,
            "reconnects": self._reconnects,
            "connected": self.connected,
        }

    # --- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        if self._running:
            return
        self._loop = asyncio.get_running_loop()
        self._wlock = asyncio.Lock()
        self._connected = asyncio.Event()
        self._fallback_pool = ThreadPoolExecutor(
            2, thread_name_prefix="verify-degrade"
        )
        self._running = True
        self._manager = self._loop.create_task(self._run())

    async def stop(self) -> None:
        self._running = False
        manager, self._manager = self._manager, None
        if manager is not None:
            manager.cancel()
            try:
                await manager
            except (asyncio.CancelledError, Exception):
                pass
        self._teardown_conn()
        # resolve anything still pending locally — stop() must not
        # strand a caller
        self._degrade_pending("client stopped")
        pool, self._fallback_pool = self._fallback_pool, None
        if pool is not None:
            # queued (not yet running) fallbacks still execute;
            # shutdown only refuses NEW work after the drain above
            pool.shutdown(wait=False)

    async def _run(self) -> None:
        backoff = self.retry_base
        while self._running:
            try:
                reader, writer = await asyncio.open_unix_connection(
                    self.path
                )
            except (ConnectionError, OSError, FileNotFoundError):
                await asyncio.sleep(backoff)
                backoff = min(self.retry_cap, backoff * 2)
                continue
            backoff = self.retry_base
            self._writer = writer
            self._connected.set()
            self._reconnects += 1
            self.metrics.reconnects.inc()
            self.logger.info(
                "verify-service attached", socket=self.path
            )
            try:
                await self._read_loop(reader)
            except (ConnectionError, OSError, WireError) as e:
                self.logger.error(
                    "verify-service connection lost", err=repr(e)
                )
            finally:
                self._teardown_conn()
                self._degrade_pending("connection lost mid-flight")

    def _teardown_conn(self) -> None:
        self._connected.clear()
        writer, self._writer = self._writer, None
        if writer is not None:
            try:
                writer.close()
            except Exception:
                pass

    async def _read_loop(self, reader) -> None:
        while True:
            frame = await read_frame(reader)
            if frame is None:
                raise ConnectionError("verify service closed the socket")
            cur = _Cursor(frame)
            typ, req_id = _HDR.unpack(cur.take(_HDR.size))
            req = self._pending.pop(req_id, None)
            if req is None:
                continue  # degraded already (e.g. raced a reconnect)
            now = time.perf_counter()
            if typ == MSG_VERDICTS and req.kind == "sig":
                self._book_rtt(req, now)
                if not req.future.done():
                    req.future.set_result(decode_verdicts(cur))
                self._last_done = (req_id, time.perf_counter_ns())
            elif typ == MSG_FN_RESULTS and req.kind == "fn":
                self._book_rtt(req, now)
                if not req.future.done():
                    req.future.set_result(decode_fn_results(cur))
                self._last_done = (req_id, time.perf_counter_ns())
            elif typ == MSG_ERROR:
                msg = cur.bytes32().decode(errors="replace")
                self._degrade_one(req, f"service error: {msg}")
            else:
                self._degrade_one(
                    req, f"mismatched response type {typ}"
                )

    def _book_rtt(self, req: _RemoteReq, now: float) -> None:
        dt = max(0.0, now - req.t0)
        self._rtt_count += 1
        self._rtt_sum += dt
        self.metrics.rtt_seconds.observe(dt)
        if req.ctx is not None:
            # the client-observed round trip, on the NODE's own ring
            # and under the height it was stamped with: the per-height
            # conservation audit bills this as verify_ipc, and the
            # cluster merge joins it (via origin+req) to the service's
            # queue/device sub-spans to expose the wire overhead
            height, round_, origin, rid = req.ctx
            self._trace().add_span(
                "verify.ipc", req.t0, dt,
                height=height, round=round_, origin=origin, req=rid,
                n=len(req.items), klass=req.klass,
            )

    # --- degradation -------------------------------------------------------

    def _degrade_event(self, reason: str, klass: str, n: int) -> None:
        self._degrades += 1
        self.metrics.degrades.inc()
        self._trace().event(DEGRADE_EVENT, reason=reason, klass=klass, n=n)

    def _degrade_one(self, req: _RemoteReq, reason: str) -> None:
        """Resolve one request through its local path on the PRIVATE
        fallback pool — never the event loop, and never the shared
        default executor (whose slots the waiting callers hold)."""
        if req.future.done():
            return
        self._degrade_event(reason, req.klass, len(req.items))
        pool = self._fallback_pool
        fut = self._loop.run_in_executor(pool, req.fallback)

        def _done(f):
            if req.future.done():
                return
            exc = f.exception()
            if exc is not None:
                req.future.set_exception(exc)
            else:
                req.future.set_result(f.result())

        fut.add_done_callback(_done)

    def _degrade_pending(self, reason: str) -> None:
        pending, self._pending = self._pending, {}
        for req in pending.values():
            self._degrade_one(req, reason)

    # --- submission --------------------------------------------------------

    async def submit(
        self, items: list[SigItem], klass: str = "consensus",
        t_gather_ns: int = 0,
    ) -> np.ndarray:
        """`t_gather_ns`: when the caller's gather of `items` began
        (`time.perf_counter_ns()`; 0: they came from no gather), for
        the service's `verify.client_gather`."""
        items = list(items)
        if not items:
            return np.zeros(0, dtype=bool)
        fallback = lambda: np.asarray(self.verifier.verify(items))  # noqa: E731
        if not self._running or not self.connected:
            if self._running:
                self._degrade_event("service unreachable", klass, len(items))
            return await asyncio.get_running_loop().run_in_executor(
                self._fallback_pool if self._running else None, fallback
            )
        return await self._send_req(
            "sig", items, klass, fallback, t_gather_ns=t_gather_ns
        )

    async def submit_fn(
        self, items: list, fn: Callable[[list], list],
        klass: str = "consensus", engine: str = "fn",
    ):
        """Closure lane: a function object cannot cross the process
        boundary, so it runs locally (off-loop) — identical semantics
        to the in-proc scheduler's degraded path. Wire-able engines go
        through submit_wire_fn instead (`engine` here is only the
        accounting label, accepted for surface parity)."""
        items = list(items)
        if not items:
            return []
        return await asyncio.get_running_loop().run_in_executor(
            None, fn, items
        )

    async def submit_wire_fn(
        self,
        engine: str,
        items: list[tuple],
        klass: str = "consensus",
        fallback: Optional[Callable[[], list]] = None,
    ):
        items = list(items)
        if not items:
            return []
        fb = fallback or (lambda: [None] * len(items))
        if not self._running or not self.connected:
            if self._running:
                self._degrade_event("service unreachable", klass, len(items))
            return await asyncio.get_running_loop().run_in_executor(
                self._fallback_pool if self._running else None, fb
            )
        return await self._send_req(
            "fn", items, klass, fb, engine=engine
        )

    async def _send_req(
        self, kind, items, klass, fallback, engine="", t_gather_ns=0
    ):
        from ..obs.tracer import height_hint

        t_submit_ns = time.perf_counter_ns()
        self._next_id += 1
        req_id = self._next_id
        # trace context: the consensus height in progress (published by
        # the state machine on every step transition) + this client's
        # identity, and readings of the clock this process shares with
        # the service (SHARED_CLOCK): this entry and, taken as the
        # trailer is appended, the end of the encode; when the caller's
        # gather began; the request finished last and when its reply
        # was decoded. Always stamped — ~55 bytes on the wire — so the
        # service side can attribute even when the client's own ring
        # is off; recording on either side stays gated on its tracer.
        height, round_ = height_hint()
        wire_ctx = (
            (height, round_, self.origin, t_submit_ns, t_gather_ns,
             *self._last_done)
            if SHARED_CLOCK else (height, round_, self.origin)
        )
        req = _RemoteReq(
            kind, items, klass, self._loop.create_future(), fallback,
            t_submit_ns * 1e-9, ctx=(height, round_, self.origin, req_id),
        )
        self._pending[req_id] = req
        try:
            payload = (
                encode_submit(req_id, items, klass, ctx=wire_ctx)
                if kind == "sig"
                else encode_submit_fn(
                    req_id, engine, items, klass, ctx=wire_ctx
                )
            )
            async with self._wlock:
                writer = self._writer
                if writer is None:
                    raise ConnectionError("not connected")
                write_frame(writer, payload)
                await writer.drain()
        except (ConnectionError, OSError, WireError) as e:
            # degrade only if WE still own the request: a teardown that
            # raced this send (read loop died while drain() was
            # suspended) already popped it via _degrade_pending — a
            # second _degrade_one would verify the batch locally twice
            # and double-count the degrade
            if self._pending.pop(req_id, None) is not None:
                self._degrade_one(req, f"send failed: {e!r}")
        else:
            self._remote_submissions += 1
            self.metrics.submissions.inc(
                klass="fn" if kind == "fn" else klass
            )
        return await req.future

    # --- thread bridges (the VerifyScheduler surface) ----------------------

    def submit_sync(
        self, items: list[SigItem], klass: str = "consensus"
    ) -> np.ndarray:
        """From a worker thread. A `SigBatch` (a commit's gather) hands
        its `t_gather_ns` on to the submission."""
        t_gather_ns = getattr(items, "t_gather_ns", 0)
        items = list(items)
        loop = self._loop
        if not self._running or loop is None or _on_loop_thread():
            return np.asarray(self.verifier.verify(items))
        if not self.connected:
            # degraded-mode fast path: run the local verify ON THE
            # CALLING worker thread instead of bouncing loop -> pool
            # (the thread already owns an executor slot; see
            # _fallback_pool). A reconnect racing this check costs one
            # extra local verify, never a wrong verdict.
            self._degrade_event("service unreachable", klass, len(items))
            return np.asarray(self.verifier.verify(items))
        try:
            fut = asyncio.run_coroutine_threadsafe(
                self.submit(items, klass, t_gather_ns), loop
            )
            return np.asarray(fut.result())
        except Exception as e:
            self.logger.error(
                "remote verify failed; direct dispatch", err=repr(e)
            )
            return np.asarray(self.verifier.verify(items))

    def submit_fn_sync(
        self, items: list, fn: Callable[[list], list],
        klass: str = "consensus", engine: str = "fn",
    ):
        # closures run on the calling worker thread — exactly where the
        # in-proc scheduler's degraded path runs them
        return fn(list(items))

    def submit_wire_fn_sync(
        self,
        engine: str,
        items: list[tuple],
        klass: str = "consensus",
        fallback: Optional[Callable[[], list]] = None,
    ):
        items = list(items)
        fb = fallback or (lambda: [None] * len(items))
        loop = self._loop
        if not self._running or loop is None or _on_loop_thread():
            return fb()
        if not self.connected:
            # same calling-thread fast path as submit_sync
            self._degrade_event("service unreachable", klass, len(items))
            return fb()
        try:
            fut = asyncio.run_coroutine_threadsafe(
                self.submit_wire_fn(engine, items, klass, fb), loop
            )
            return fut.result()
        except Exception as e:
            self.logger.error(
                "remote fn-lane verify failed; local fallback",
                err=repr(e),
            )
            return fb()

    def classed(self, klass: str) -> _ClassedVerifier:
        """BatchVerifier-shaped handle submitting under `klass` (the
        same adapter the in-proc scheduler hands out — it only needs
        submit_sync + .verifier)."""
        return _ClassedVerifier(self, klass)


def _on_loop_thread() -> bool:
    try:
        asyncio.get_running_loop()
    except RuntimeError:
        return False
    return True


# --- standalone runtime ------------------------------------------------------


def run_service(
    path: str,
    max_batch: int = 16384,
    stats_port: Optional[int] = None,
    prewarm: bool = False,
    logger: Optional[Logger] = None,
    ready_fd: Optional[int] = None,
    trace: bool = False,
) -> int:
    """Blocking service runtime for the CLI entrypoint: open the device
    (this process owns it — a chip that cannot be opened fails the
    start, not the first round), build the scheduler, optionally
    AOT-prewarm the bucket ladder (a failed prewarm fails the start
    too), serve until SIGINT/SIGTERM. `ready_fd` (harness use) gets
    one JSON line ({"ready": true, "stats_port": N}) written when the
    socket is accepting — spawners wait on it instead of polling.
    `trace` (or TM_TPU_TRACE=1) arms the service flight ring
    (SERVICE_RING_SIZE records) served at GET /dump_traces on the stats
    port."""
    import signal

    from ..obs import Tracer, set_default_tracer

    logger = logger or nop_logger()
    configure_compile_cache()
    compile_log()  # listen from the first compile on
    logger.info("verify service device", **device_info())
    tracer = set_default_tracer(
        Tracer(
            enabled=trace or os.environ.get("TM_TPU_TRACE") == "1",
            ring_size=SERVICE_RING_SIZE,
        )
    )
    server = VerifyServiceServer(
        path, max_batch=max_batch, logger=logger, stats_port=stats_port,
        tracer=tracer,
    )

    async def run() -> None:
        await server.start()
        try:
            if prewarm:
                entries = server.scheduler.verifier.prewarm_buckets()
                logger.info(
                    "verify-service prewarm complete",
                    programs=len(entries),
                )
            if ready_fd is not None:
                os.write(
                    ready_fd,
                    json.dumps(
                        {"ready": True, "stats_port": server.stats_port}
                    ).encode(),
                )
                os.close(ready_fd)
            stop = asyncio.Event()
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                try:
                    loop.add_signal_handler(sig, stop.set)
                except (NotImplementedError, RuntimeError):
                    pass
            await stop.wait()
        finally:
            await server.stop()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


class ServiceThread:
    """In-process service on its own event-loop thread — the unit-test
    and single-process-harness runtime (the production topology runs
    `python -m tendermint_tpu verify-service` instead)."""

    def __init__(self, path: str, **kw):
        self.server = VerifyServiceServer(path, **kw)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        started = threading.Event()

        def run():
            loop = asyncio.new_event_loop()
            self._loop = loop
            asyncio.set_event_loop(loop)
            loop.run_until_complete(self.server.start())
            started.set()
            loop.run_forever()
            loop.run_until_complete(self.server.stop())
            loop.close()

        self._thread = threading.Thread(
            target=run, name="verify-service", daemon=True
        )
        self._thread.start()
        if not started.wait(30):
            raise RuntimeError("verify service failed to start")

    def stop(self) -> None:
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._loop.stop)
        if self._thread is not None:
            self._thread.join(timeout=30)
        self._loop = self._thread = None
