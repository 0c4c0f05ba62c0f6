"""The processes of one run: the chip's owner and the load child. The
parent (run.py) never imports JAX: a chip belongs to one process."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

from harness.cell import BENCH_DIR, ROOT

READY_TIMEOUT = 300.0
STOP_TIMEOUT = 60.0


class Procs:
    """Every process the run starts, so that all of them are stopped
    and waited for whatever happens."""

    def __init__(self):
        self.live: list = []

    def spawn(self, cmd, log_path, **kw) -> subprocess.Popen:
        with open(log_path, "ab") as log:
            proc = subprocess.Popen(
                cmd, cwd=ROOT, stdout=log, stderr=log, **kw
            )
        self.live.append(proc)
        return proc

    def stop_all(self) -> None:
        for p in self.live:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + STOP_TIMEOUT
        for p in self.live:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()


def tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def service_command(work: str, sock: str, wfd: int, trace: bool) -> list:
    """Untraced: the entry point users call, at its defaults. Traced:
    the same run_service under service_host.py."""
    if not trace:
        return [
            sys.executable, "-m", "tendermint_tpu", "verify-service",
            "--socket", sock, "--stats-port", "0", "--ready-fd", str(wfd),
        ]
    return [
        sys.executable,
        os.path.join(BENCH_DIR, "harness", "service_host.py"),
        "--socket", sock, "--ready-fd", str(wfd),
        "--control", os.path.join(work, "ctl.sock"),
        "--trace-dir", os.path.join(work, "trace"),
    ]


def start_service(procs: Procs, work: str, command) -> tuple:
    """Start the chip's owner; (proc, socket, stats port, log) once it
    signalled ready. Paths are relative to the checkout, the children's
    working directory, so a deep checkout cannot outgrow a socket name."""
    sock = os.path.join(work, "vs.sock")
    log = os.path.join(ROOT, work, "service.log")
    rfd, wfd = os.pipe()
    proc = procs.spawn(command(work, sock, wfd), log, pass_fds=(wfd,))
    os.close(wfd)
    os.set_blocking(rfd, False)
    ready = b""
    deadline = time.monotonic() + READY_TIMEOUT
    try:
        while not ready and time.monotonic() < deadline:
            if proc.poll() is not None:
                break
            try:
                ready = os.read(rfd, 4096)
            except BlockingIOError:
                time.sleep(0.05)
    finally:
        os.close(rfd)
    if not ready:
        raise SystemExit(
            f"verify service never signalled ready (rc={proc.poll()}):\n"
            + tail(log)
        )
    return proc, sock, json.loads(ready)["stats_port"], log


def service_get(port: int, path: str = "dump_dispatch_ledger") -> dict:
    """The service's own account of itself, from its stats port."""
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/{path}", timeout=60.0
    ) as resp:
        return json.loads(resp.read())


def require_chip(service: dict, chips: int) -> None:
    """libs/device.require_chip's rule, applied to what the SERVICE
    resolved: no accelerator, or fewer chips than the cell asks for,
    ends the run non-zero with no result. JAX_PLATFORMS=cpu, set by
    hand, makes a rehearsal whose line says `cpu`."""
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if service["platform"] != "tpu" and not rehearsal:
        raise SystemExit(
            f"no accelerator: the service resolved {service['platform']!r}"
        )
    if service["platform"] == "tpu" and service["device_count"] < chips:
        raise SystemExit(
            f"the cell asks for {chips} chips, the service found "
            f"{service['device_count']}"
        )
