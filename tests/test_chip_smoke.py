"""chip_smoke.py on the CPU at its tiny size, and the rules it rests on.

The smoke is the quickest proof that the system still starts on the
chip, so chip time must never be spent on its own bugs: here it runs
end to end at 8 validators x 2 commits under an explicit
JAX_PLATFORMS=cpu, and it must FAIL when an expected verdict is wrong,
when the service dies mid-window (a degrade is a failure, not
something to absorb), and when there is no chip and nobody asked for
the CPU. Beside it: one process for each chip (a node assembled with
`remote_socket` is a CPU process) and one compile-cache policy.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = ["--validators", "8", "--commits", "2", "--live", "8"]


def _smoke(*args, env_drop=(), timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # conftest's 8 virtual devices are the test process's, not a user's
    for k in ("XLA_FLAGS", *env_drop):
        env.pop(k, None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *TINY, *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_chip_smoke_tiny_passes_on_the_cpu():
    proc = _smoke()
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    out = proc.stdout
    assert "platform: cpu" in out
    assert "device readings: not measured" in out
    assert "bls12_381 loaded" in out
    assert "0 compilations in the warm pass" in out
    assert "exit 0 on SIGTERM, no traceback" in out
    # the client node ran beside a live service without opening a
    # device of its own, and the second service hit the compile cache
    assert "node platform cpu" in out and "0 degrades" in out
    assert "cache_misses 0" in out


def test_chip_smoke_fails_on_a_flipped_expected_verdict():
    proc = _smoke("--stages", "service", "--fault", "flip-verdict")
    assert proc.returncode != 0
    assert "window bitmap differs" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


def test_chip_smoke_fails_when_the_service_dies_mid_window():
    proc = _smoke("--stages", "service", "--fault", "kill-service")
    assert proc.returncode != 0
    assert "degraded" in proc.stderr  # the tripwire, not a host fallback
    assert '"ok"' not in proc.stdout


def test_chip_smoke_fails_without_a_chip():
    """No accelerator and no explicit JAX_PLATFORMS=cpu: the service
    falls back to the CPU silently, the smoke does not. And the full
    size needs the chip whatever JAX_PLATFORMS says."""
    proc = _smoke("--stages", "service", env_drop=("JAX_PLATFORMS",))
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert '"ok"' not in proc.stdout
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--stages", "service"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "the full size needs the chip" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_node_stage_fails_when_the_node_fell_back_to_the_cpu(
    tmp_path, monkeypatch
):
    """The node stage is held to the chip like the service: a node that
    found the chip still taken falls back to the CPU with a warning and
    commits all the same, so the stage reads the platform the node
    logged. Here the real stage runs with no JAX_PLATFORMS: 5 commits,
    a clean exit, platform=cpu in the log, and the stage fails."""
    import chip_smoke

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    procs = chip_smoke.Procs()
    try:
        with pytest.raises(SystemExit, match="no accelerator"):
            chip_smoke.stage_node(procs, str(tmp_path), "tpu")
    finally:
        procs.kill_all()
    log = str(tmp_path / "node.log")
    assert chip_smoke.log_fields(log, "node device")["platform"] == "cpu"
    # and under an explicit JAX_PLATFORMS=cpu the node must still have
    # opened what the service before it did
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit, match="the node opened platform 'cpu'"):
        chip_smoke.node_device(log, "tpu")
    assert chip_smoke.node_device(log, "cpu")["platform"] == "cpu"


def test_remote_socket_node_is_a_cpu_process(tmp_path):
    """Node assembly decides from what it can observe: remote_socket is
    set, so this process's local fallback verifier is a CPU verifier.
    Run in a child with JAX_PLATFORMS asking for something else, so
    that the pin — not the test environment — is what decides."""
    script = """
import sys
from tendermint_tpu.config import Config
from tendermint_tpu.node import Node, init_files

cfg = Config()
cfg.root_dir = sys.argv[1]
cfg.base.db_backend = "memory"
cfg.rpc.laddr = ""
cfg.scheduler.remote_socket = "verify.sock"
init_files(cfg)
node = Node(cfg)
import jax
from tendermint_tpu.parallel.verify_service import RemoteVerifyScheduler
assert isinstance(node.verify_scheduler, RemoteVerifyScheduler)
assert jax.config.jax_platforms == "cpu", jax.config.jax_platforms
verifier = node.verify_scheduler.verifier  # the local fallback
import jax.numpy as jnp
print("platform", jnp.zeros(1).devices().pop().platform)
"""
    env = dict(os.environ, JAX_PLATFORMS="tpu,cpu")
    proc = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "platform cpu" in proc.stdout
    assert "node device" in proc.stdout + proc.stderr
    assert "platform=cpu" in proc.stdout + proc.stderr


_CACHE_PROBE = """
import os, sys
import jax
from tendermint_tpu.libs.jax_cache import configure_compile_cache
path = configure_compile_cache()
assert jax.config.jax_compilation_cache_dir == path
if len(sys.argv) > 1:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7)).block_until_ready()
print(path)
"""


def _cache_probe(env, *args) -> str:
    proc = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.strip().splitlines()[-1]


def _files_under(path: str) -> set:
    return {
        os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
    }


def test_one_compile_cache_policy(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    # without the override: the same path from two processes, a
    # function of the checkout and the machine only
    a, b = _cache_probe(env), _cache_probe(env)
    assert a == b
    assert a.startswith(os.path.join(REPO, ".jax_cache") + os.sep)
    # with it: that directory and nothing else
    repo_before = _files_under(os.path.join(REPO, ".jax_cache"))
    home_cache = os.path.expanduser("~/.cache/tendermint_tpu")
    home_before = _files_under(home_cache)
    target = tmp_path / "xla-cache"
    env["JAX_COMPILATION_CACHE_DIR"] = str(target)
    assert _cache_probe(env, "compile") == str(target)
    assert _files_under(str(target)), "nothing was cached in the override"
    assert _files_under(os.path.join(REPO, ".jax_cache")) == repo_before
    assert _files_under(home_cache) == home_before
