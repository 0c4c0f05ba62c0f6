"""The chip's owner in a traced run.

`verify-service` has no profiler hook, and only the process that holds
the chip can trace it. So with `--trace 1` the parent starts this file
in place of `python -m tendermint_tpu verify-service`: it calls the same
`parallel.verify_service.run_service` on its main thread, with the
CLI's defaults and the service's own span ring armed, and from a side
thread on a control socket wraps the span the load child names in
`jax.profiler.start_trace` / `stop_trace`. The program is not edited.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, ROOT)


class Session:
    """One profiler session. `jax.profiler.stop_trace` also converts
    the trace for a viewer, which took 44 s for a 5-second span of the
    big tier (chip run, PR 24); the session's own `stop()` hands back
    the same XSpace in a fraction of that. Where this JAX has no such
    session, the public pair does the same job slowly."""

    def __init__(self, trace_dir: str):
        import jax

        self.dir = trace_dir
        self.options = jax.profiler.ProfileOptions()
        self.options.python_tracer_level = 0  # device and XLA host events
        try:
            from jax._src.lib import _profiler
        except ImportError:
            _profiler = None
        self.raw = _profiler
        self.session = None
        jax.devices()  # the backend is up before a session starts

    def start(self) -> None:
        import jax

        if self.raw is None:
            jax.profiler.start_trace(self.dir, profiler_options=self.options)
        else:
            self.session = self.raw.ProfilerSession(self.options)

    def stop(self) -> None:
        import jax

        if self.raw is None:
            jax.profiler.stop_trace()
            return
        xspace, self.session = self.session.stop(), None
        out = os.path.join(self.dir, "plugins", "profile", "span")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "host.xplane.pb"), "wb") as f:
            f.write(xspace)


def control(listener: socket.socket, trace_dir: str) -> None:
    session = Session(trace_dir)
    while True:
        conn, _ = listener.accept()
        with conn, conn.makefile("rwb") as f:
            word = f.readline().strip().decode()
            before = time.time_ns()
            if word == "start":
                session.start()
            mark = {
                "word": word,
                "wall_ns_before": before,
                "perf_counter": time.perf_counter(),
                "wall_ns": time.time_ns(),
            }
            if word == "stop":
                session.stop()
                mark["stop_s"] = time.perf_counter() - mark["perf_counter"]
            f.write(json.dumps(mark).encode() + b"\n")
            f.flush()


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--socket", required=True)
    p.add_argument("--ready-fd", type=int, required=True)
    p.add_argument("--control", required=True)
    p.add_argument("--trace-dir", required=True)
    args = p.parse_args()

    from tendermint_tpu.libs.jax_cache import configure_compile_cache
    from tendermint_tpu.libs.log import default_logger
    from tendermint_tpu.parallel.verify_service import run_service

    configure_compile_cache()
    listener = socket.socket(socket.AF_UNIX)
    listener.bind(args.control)
    listener.listen(1)
    threading.Thread(
        target=control, args=(listener, args.trace_dir), daemon=True
    ).start()
    return run_service(
        args.socket, stats_port=0, logger=default_logger(),
        ready_fd=args.ready_fd, trace=True,
    )


if __name__ == "__main__":
    sys.exit(main())
