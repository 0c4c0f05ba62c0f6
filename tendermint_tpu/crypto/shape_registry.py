"""Process-wide registry of device verify-program shapes.

PERF_ANALYSIS §10's cold bisect-1k capture loaded 44 distinct op-shape
XLA programs — every ad-hoc batch size that reaches the device is its
own program, and each one is a compile (or a cache load) some caller
waits for. The countermeasure is shape discipline: every dispatch pads to a canonical bucket from ONE
geometric ladder, so the whole node executes from a handful of
precompiled programs per tier.

This module owns that ladder and the process-wide accounting:

- `bucket_for(n, multiple_of)` — the canonical padded size every
  verify dispatch uses (BatchVerifier and the dispatch scheduler both
  route here, so a config override changes every caller at once);
- `record_dispatch(tier, bucket)` — called by BatchVerifier._dispatch
  for every device round, counting distinct (tier, bucket, devices)
  program shapes and total dispatches. A mesh-sharded round compiles a
  DIFFERENT XLA program than the single-device round at the same
  bucket (the sharding is part of the lowering), so `devices` is a
  first-class shape dimension: per-mesh programs stay inside the same
  budget accounting as everything else. The service dump and the
  `dump_dispatch_ledger` RPC carry a snapshot, and the shape-budget
  regression test (tests/test_prewarm.py) asserts the vote and commit
  verify shapes stay within a bounded ladder.

Stdlib only; thread-safe (dispatches happen from executor threads, the
scheduler's dispatch thread, and test harness threads concurrently).
"""

from __future__ import annotations

import threading

# Canonical bucket ladder: small buckets for consensus latency (votes
# trickle in), large for blocksync/light bulk replay. 16384 is the
# measured throughput knee of the bulk tier (PERF_ANALYSIS §10: 32768
# buys +4% for 2x per-batch latency). 256 is the committee-scale rung
# (PERF_ANALYSIS §16): batched vote gossip and batch-point BLS bursts
# at 100-200 validators land whole-committee chunks that would
# otherwise pad 129-vote batches all the way to 512 (fill 0.25 at 129
# vs 0.5+ on the 256 rung). Batches beyond the top rung pad to
# multiples of it. Override per-process with `configure_default`
# (node assembly applies [scheduler] bucket_ladder before the first
# verifier is built).
DEFAULT_BUCKET_LADDER = (8, 32, 128, 256, 512, 2048, 8192, 16384)


class ShapeRegistry:
    """Bucket ladder + (tier, bucket) program-shape accounting."""

    def __init__(self, ladder=DEFAULT_BUCKET_LADDER):
        ladder = tuple(sorted({int(b) for b in ladder}))
        if not ladder or ladder[0] < 1:
            raise ValueError(f"invalid bucket ladder {ladder!r}")
        self.ladder = ladder
        self._lock = threading.Lock()
        # tier -> set of (bucket, rows, devices): a program's shape is
        # the batch bucket AND any secondary operand dimension that
        # varies — the cached tiers' table-store row count (_TableCache
        # grows it in powers of two, so rows has its own small ladder;
        # rows=0 for tiers without one) and the mesh device count the
        # batch axis shards over (1 = unsharded; a sharded program is a
        # distinct lowering even at the same bucket)
        self._shapes: dict[str, set[tuple[int, int, int]]] = {}
        self._dispatches = 0
        self._sharded_dispatches = 0

    # --- bucketing --------------------------------------------------------

    def bucket_for(self, n: int, multiple_of: int = 1) -> int:
        """Smallest ladder bucket >= n, rounded up so the batch axis
        divides evenly across `multiple_of` mesh shards. Beyond the top
        rung, multiples of it (one extra shape per rung-multiple, not
        one per batch size)."""
        base = next((b for b in self.ladder if b >= n), None)
        if base is None:
            q = self.ladder[-1]
            base = ((n + q - 1) // q) * q
        m = multiple_of
        return ((base + m - 1) // m) * m

    # --- accounting -------------------------------------------------------

    def record_dispatch(
        self, tier: str, bucket: int, rows: int = 0, devices: int = 1
    ) -> bool:
        """Count one device dispatch; True iff (tier, bucket, rows,
        devices) is a shape this registry has not seen before. `rows` is
        the secondary shape dimension for tiers whose programs also vary
        with the table-store allocation (0 when not applicable);
        `devices` is the mesh shard count of the batch axis (1 =
        unsharded)."""
        with self._lock:
            self._dispatches += 1
            if devices > 1:
                self._sharded_dispatches += 1
            seen = self._shapes.setdefault(tier, set())
            key = (int(bucket), int(rows), int(devices))
            if key in seen:
                return False
            seen.add(key)
            return True

    def distinct_shapes(self, tier: str | None = None) -> int:
        with self._lock:
            if tier is not None:
                return len(self._shapes.get(tier, ()))
            return sum(len(s) for s in self._shapes.values())

    def dispatch_count(self) -> int:
        with self._lock:
            return self._dispatches

    def sharded_dispatch_count(self) -> int:
        """Dispatches whose batch axis was sharded over > 1 device."""
        with self._lock:
            return self._sharded_dispatches

    def shapes_by_tier(
        self,
    ) -> dict[str, tuple[tuple[int, int, int], ...]]:
        """tier -> sorted ((bucket, rows, devices), ...) shapes seen."""
        with self._lock:
            return {t: tuple(sorted(s)) for t, s in self._shapes.items()}

    def buckets_by_tier(self) -> dict[str, tuple[int, ...]]:
        """tier -> sorted distinct batch buckets (rows/devices
        collapsed)."""
        with self._lock:
            return {
                t: tuple(sorted({b for b, _, _ in s}))
                for t, s in self._shapes.items()
            }

    def snapshot(self) -> dict:
        """Point-in-time view; feed two of these to `delta`."""
        with self._lock:
            return {
                "distinct_program_shapes": sum(
                    len(s) for s in self._shapes.values()
                ),
                "device_dispatch_count": self._dispatches,
                "sharded_dispatch_count": self._sharded_dispatches,
                "shapes_by_tier": {
                    t: sorted(list(k) for k in s)
                    for t, s in self._shapes.items()
                },
            }

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        """New-shapes/dispatches between two snapshots. The sharded
        count rides next to device_dispatch_count so a reader sees
        whether the rounds in between actually went through the mesh
        (a meshless run records sharded = 0)."""
        return {
            "distinct_program_shapes": (
                after["distinct_program_shapes"]
                - before["distinct_program_shapes"]
            ),
            "device_dispatch_count": (
                after["device_dispatch_count"]
                - before["device_dispatch_count"]
            ),
            "sharded_dispatch_count": (
                after.get("sharded_dispatch_count", 0)
                - before.get("sharded_dispatch_count", 0)
            ),
        }


_default: ShapeRegistry | None = None
_default_lock = threading.Lock()


def default_shape_registry() -> ShapeRegistry:
    """Process-wide registry every BatchVerifier records into unless
    handed an explicit one (tests isolate with their own instance)."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = ShapeRegistry()
    return _default


def configure_default(ladder) -> ShapeRegistry:
    """Install a fresh default registry with `ladder` (node assembly,
    from [scheduler] bucket_ladder). Must run before the first verifier
    dispatch or earlier shape counts are lost — which is why node
    assembly does this in __init__, ahead of any reactor's first
    verify."""
    global _default
    with _default_lock:
        _default = ShapeRegistry(ladder)
    return _default
