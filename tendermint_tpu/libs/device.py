"""Which device this process runs on, and who may open the chip.

A TPU belongs to one process at a time: a second process that
initialises the default JAX backend while the first holds the chip
fails or hangs. So the process that owns the device plane (a node with
an in-proc scheduler, or the verify service) is the only one that may
resolve the default backend; every process beside it pins itself to
the CPU platform BEFORE its first JAX call.
"""

from __future__ import annotations

import os


def device_stamp() -> dict:
    """platform / device_kind / device_count as JAX reports them: the
    provenance block of every artifact and log line. Opens the default
    backend — call it only from the process that owns the device."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
    }


def device_info() -> dict:
    """device_stamp() plus bytes_in_use, peak_bytes_in_use and
    bytes_limit where the backend keeps memory stats (TPU does, XLA:CPU
    returns None)."""
    import jax

    info = device_stamp()
    stats = jax.devices()[0].memory_stats() or {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            info[key] = int(stats[key])
    return info


def require_chip(platform: str) -> None:
    """The one rule for anything that measures or smoke-tests the
    device path: JAX falls back to the CPU silently when it finds no
    TPU, so unless JAX_PLATFORMS=cpu was asked for explicitly, a
    resolved platform other than `tpu` ends the run non-zero."""
    if platform == "tpu":
        return
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    raise SystemExit(
        f"no accelerator: JAX resolved platform {platform!r}, not 'tpu' "
        "(set JAX_PLATFORMS=cpu explicitly for a CPU debugging run)"
    )


def pin_cpu() -> None:
    """This process runs beside a chip owner (a verify-service client):
    keep JAX off the chip. Effective only before the first backend
    initialisation, which is why callers run it at assembly."""
    import jax

    jax.config.update("jax_platforms", "cpu")
