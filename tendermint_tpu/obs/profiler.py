"""On-demand profiling hooks — device trace + sampled event-loop
profile, armed over RPC (`profile_start`/`profile_stop`).

A live node on the chip must be minable without a redeploy: when an
anomaly shows up mid-run, the operator starts a bounded profile
against the RUNNING node, pulls the artifacts from `data/profiles/`,
and keeps serving. Two captures per session:

- **device trace**: a jax profiler session (the session object
  itself where this JAX has it, else `jax.profiler.start_trace(dir)`)
  when the jax profiler is importable and startable; the trace lands
  as `<dir>/plugins/profile/*/*.xplane.pb` — guarded, CPU-backend
  tolerant (the CPU backend records a host-side XPlane trace; a
  missing/broken
  profiler degrades to a structured `{"enabled": false, "error": ...}`
  in the session record, never an exception out of the RPC);
- **sampled event-loop profile**: a daemon thread samples the event
  loop thread's stack (`sys._current_frames()`) on a fixed interval
  and aggregates identical stacks — the PR 9/11 finding is that the
  event LOOP, not the device, is the binding resource past ~32
  validators, and `tm_event_loop_lag_seconds` says THAT it's slow
  while this says WHERE. Written as JSON (stack -> sample count,
  hottest first) at stop.

One session at a time (a second start is a caller error, surfaced as a
structured RPC error by rpc/core). Stdlib except the guarded jax
import; no clock reads outside the session driver itself — session
ids come from a monotonic counter, not wall time.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import Counter
from typing import Optional


class ProfilerUnavailable(RuntimeError):
    """The requested capture cannot run (already active / not active /
    device profiler required but missing). rpc/core maps this to a
    structured JSON-RPC error."""


class _StackSampler(threading.Thread):
    """Samples one thread's Python stack on a fixed interval."""

    def __init__(self, target_thread_id: int, interval_s: float):
        super().__init__(name="obs/profile-sampler", daemon=True)
        self.target_thread_id = target_thread_id
        self.interval_s = interval_s
        self.samples = 0
        self.stacks: Counter = Counter()
        # NOT named _stop: Thread._stop is a real (private) CPython
        # method that join() calls — shadowing it with an Event breaks
        # every join with "'Event' object is not callable"
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.wait(self.interval_s):
            frame = sys._current_frames().get(self.target_thread_id)
            if frame is None:
                continue
            stack = []
            depth = 0
            while frame is not None and depth < 64:
                code = frame.f_code
                stack.append(
                    f"{os.path.basename(code.co_filename)}:"
                    f"{frame.f_lineno}:{code.co_name}"
                )
                frame = frame.f_back
                depth += 1
            # innermost-first; key on the tuple so identical stacks fold
            self.stacks[tuple(stack)] += 1
            self.samples += 1

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=2.0)


class ProfileCapture:
    """One-at-a-time profiling sessions writing into `out_dir`
    (data/profiles under the node home). `start()` returns the session
    record; `stop()` finalizes it with artifact paths + the loop
    profile's top stacks."""

    def __init__(
        self,
        out_dir: str,
        sample_interval_s: float = 0.01,
        logger=None,
    ):
        self.out_dir = out_dir
        self.sample_interval_s = sample_interval_s
        self.logger = logger
        self._lock = threading.Lock()
        self._session: Optional[dict] = None
        self._sampler: Optional[_StackSampler] = None
        self._device_tracing = False
        self._raw_session = None
        self._next_id = 1

    @property
    def active(self) -> bool:
        return self._session is not None

    # --- session lifecycle -----------------------------------------------

    def start(
        self,
        label: str = "",
        device: bool = True,
        thread_id: Optional[int] = None,
        python_tracer: bool = True,
    ) -> dict:
        """Arm a session. `device=False` skips the jax trace (loop
        profile only). `thread_id` is the thread whose stack is sampled
        (the event loop's, for a caller that starts the session from
        another thread; default: the calling thread). `python_tracer`
        False keeps the jax trace to device and XLA host events, where
        this JAX has profiler options. Raises ProfilerUnavailable when
        a session is already running."""
        with self._lock:
            if self._session is not None:
                raise ProfilerUnavailable(
                    f"profile session {self._session['id']!r} already "
                    "running; call profile_stop first"
                )
            sid = f"profile_{self._next_id:04d}"
            self._next_id += 1
            session_dir = os.path.join(self.out_dir, sid)
            os.makedirs(session_dir, exist_ok=True)
            device_state = {"enabled": False}
            if device:
                device_state = self._start_device_trace(
                    session_dir, python_tracer
                )
            sampler = _StackSampler(
                thread_id or threading.get_ident(), self.sample_interval_s
            )
            sampler.start()
            self._sampler = sampler
            self._session = {
                "id": sid,
                "label": label,
                "dir": session_dir,
                "t_start": time.monotonic(),
                "device_trace": device_state,
                "loop_sample_interval_s": self.sample_interval_s,
            }
            out = dict(self._session)
            out.pop("t_start")
            return out

    def stop(self) -> dict:
        """Disarm; returns the finalized session record with artifact
        paths. Raises ProfilerUnavailable when nothing is running."""
        with self._lock:
            session = self._session
            if session is None:
                raise ProfilerUnavailable(
                    "no profile session running; call profile_start first"
                )
            self._session = None
            sampler, self._sampler = self._sampler, None
        session["duration_s"] = round(
            time.monotonic() - session.pop("t_start"), 3
        )
        if self._device_tracing:
            session["device_trace"] = dict(
                session["device_trace"],
                **self._stop_device_trace(session["dir"]),
            )
        if sampler is not None:
            sampler.stop()
            session["loop_profile"] = self._write_loop_profile(
                session["dir"], sampler
            )
        return session

    # --- device trace (guarded jax) ---------------------------------------

    def _start_device_trace(
        self, session_dir: str, python_tracer: bool = True
    ) -> dict:
        """Open the jax profiler session. Where this JAX has the
        session object itself, hold it: its `stop()` hands back the
        trace as it is, while `jax.profiler.stop_trace` also exports
        it, which on a TPU took 44 s for a 5-second span of bulk rounds
        and had not ended after 117 s for 3 seconds of small-tier
        rounds."""
        try:
            import jax

            options = getattr(jax.profiler, "ProfileOptions", None)
            if options is not None:
                options = options()
                if not python_tracer:
                    options.python_tracer_level = 0
            try:
                from jax._src.lib import _profiler
            except ImportError:
                _profiler = None
            if options is None:
                jax.profiler.start_trace(session_dir)
            elif _profiler is None:
                jax.profiler.start_trace(
                    session_dir, profiler_options=options
                )
            else:
                jax.devices()  # the backend is up before a session starts
                self._raw_session = _profiler.ProfilerSession(options)
        except Exception as e:  # missing jax, no backend, double-trace
            if self.logger is not None:
                self.logger.error(
                    "device trace unavailable", err=repr(e)
                )
            return {"enabled": False, "error": repr(e)[:400]}
        self._device_tracing = True
        return {"enabled": True, "dir": session_dir}

    def _stop_device_trace(self, session_dir: str) -> dict:
        self._device_tracing = False
        raw, self._raw_session = self._raw_session, None
        try:
            if raw is None:
                import jax

                jax.profiler.stop_trace()
                return {}
            # where `jax.profiler.stop_trace` would have put it
            out = os.path.join(
                session_dir, "plugins", "profile",
                os.path.basename(session_dir),
            )
            os.makedirs(out, exist_ok=True)
            path = os.path.join(out, "host.xplane.pb")
            with open(path, "wb") as f:
                f.write(raw.stop())
            return {"xplane": path, "bytes": os.path.getsize(path)}
        except Exception as e:
            if self.logger is not None:
                self.logger.error(
                    "device trace stop failed", err=repr(e)
                )
            return {"stop_error": repr(e)[:400]}

    # --- loop profile -----------------------------------------------------

    @staticmethod
    def _write_loop_profile(session_dir: str, sampler: _StackSampler) -> dict:
        top = [
            {"count": count, "stack": list(stack)}
            for stack, count in sampler.stacks.most_common(64)
        ]
        doc = {
            "samples": sampler.samples,
            "interval_s": sampler.interval_s,
            "stacks": top,
        }
        path = os.path.join(session_dir, "loop_profile.json")
        try:
            with open(path, "w") as f:
                json.dump(doc, f, indent=1)
        except OSError:
            path = ""
        return {
            "samples": sampler.samples,
            "path": path,
            "top_stacks": top[:8],
        }
