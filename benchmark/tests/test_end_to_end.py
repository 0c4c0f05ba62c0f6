"""One tiny cell through run.py on the CPU: the result line's keys (for
the default committee kind and for `mixed_keys`), a run with no chip, a
traced run whose pool runs dry, one in which tracing never began, and a
run with the timed path broken underneath."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH_DIR, ROOT

REHEARSAL = os.path.join(BENCH_DIR, "tests", "BENCHMARK.rehearsal.json")
RUN = [
    sys.executable, os.path.join(BENCH_DIR, "run.py"),
    "--seed", "2147483999", "--seconds", "1",
    "--benchmark-file", REHEARSAL,
]


def run(workload, trace, env, more=()):
    return subprocess.run(
        RUN + ["--workload", workload, "--trace", str(trace), *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )


def cpu_env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.mark.parametrize(
    "workload,trace,metric",
    [
        ("rehearsal.catchup", 0, "catchup_commits_per_s"),
        ("rehearsal.live", 1, "queue_wait_ms.live"),
        ("rehearsal.mixed-catchup", 0, "catchup_commits_per_s"),
        ("rehearsal.mixed-live", 1, "queue_wait_ms.live"),
    ],
)
def test_result_line_has_the_contracts_keys(workload, trace, metric):
    done = run(workload, trace, cpu_env())
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"
    ]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # never under a chip's name
    assert set(line["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"
    }
    assert metric in line["metrics"]
    assert ("setup_s" in line["metrics"]) == (trace == 0)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    stderr = done.stderr.strip().splitlines()
    assert stderr[-1] == "correct True"
    assert stderr[-2].startswith("check ")
    # a share of a roofline or of the device is left out, never 0, where
    # there was no device trace to read
    assert not any("roofline" in k or "idle" in k for k in line["metrics"])
    assert set(line["window"]["setup"]) == {
        "warm_s", "signed_s", "reference_s", "rows_judged"
    }
    assert line["window"]["setup"]["signed_s"] > 0
    mixed = "mixed" in workload
    assert ("secp256k1_plain_vs_openssl" in line["checks"]) == mixed
    for check in ("rows_wrong", "commits_wrong", "plan_vs_reference",
                  "degrades", "rfc8032_vs_openssl"):
        assert line["checks"][check] == {"value": 0, "limit": 0}
    # the generator's own tally of rows, by key type, as the readers get it
    sys.path.insert(0, BENCH_DIR)
    import run as bench_run

    with open(os.path.join(ROOT, ".bench_work", workload, "report.json")) as f:
        report = json.load(f)
    ctx = bench_run.context(report, None)
    by_type = ctx["traced_rows_by_key_type"]
    assert sum(by_type.values()) == ctx["traced_rows"]
    assert (ctx["traced_rows"] > 0) == (trace == 1)
    if trace:
        assert set(by_type) == (
            {"ed25519", "secp256k1"} if mixed else {"ed25519"}
        )
        assert not mixed or by_type["ed25519"] == 3 * by_type["secp256k1"]


def test_the_open_loop_sends_within_half_a_millisecond_of_due():
    """A rehearsal run of 40 requests: the generator keeps its own
    schedule (`generators/open_arrivals.py`), so the median request
    goes out within half a millisecond of its due time (a timer alone
    reads 0.9 ms here, 1.3 on the chip's host). The median and not
    `gen_late_p95_ms`: on the CPU the service's "device" is the cores
    this process runs on, and a few requests of 40 are descheduled for
    milliseconds whatever the generator does."""
    done = run("rehearsal.live", 1, cpu_env(), ["--seconds", "8"])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["attempted"] == 40
    assert line["metrics"]["gen_late_p95_ms.live"]["value"] > 0
    with open(
        os.path.join(ROOT, ".bench_work", "rehearsal.live", "report.json")
    ) as f:
        requests = json.load(f)["requests"]
    late = sorted(r["t_sent"] - r["t_due"] for r in requests)
    assert 0 <= late[0] and late[len(late) // 2] < 0.0005


def test_the_roofline_counts_the_rows_of_the_key_type_its_file_names(
    monkeypatch
):
    sys.path.insert(0, BENCH_DIR)
    import work
    from readers import kernel_roofline

    ctx = {
        "trace": {"modules": {"jit__verify_cached_big": (2, 0.5)}},
        "traced_rows": 400,
        "traced_rows_by_key_type": {"ed25519": 300, "secp256k1": 100},
        "device": {"kind": "TPU v5 lite"},
    }
    spec = {"programs": "^jit__verify_cached_"}
    least = lambda rows: work.least_seconds(rows, "TPU v5 lite")[0]  # noqa: E731
    assert kernel_roofline.read(ctx, spec) == 100.0 * least(400) / 0.5
    assert kernel_roofline.read(
        ctx, dict(spec, key_type="ed25519")
    ) == 100.0 * least(300) / 0.5
    # nothing of that type traced: nothing to read, never a share of 0
    assert kernel_roofline.read(ctx, dict(spec, key_type="bls12-381")) is None


def test_a_control_the_kind_does_not_have_is_refused():
    done = run("rehearsal.catchup", 0, cpu_env(),
               ["--control-guarantee", "low_s"])
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "has s_range" in done.stderr


def test_a_pool_that_runs_dry_is_still_traced():
    """Five windows against 5 s: the pool is dry within a second, and
    the traced span lies before the pool's end, not before `seconds`."""
    cell = "rehearsal.catchup-dry"
    done = run(cell, 1, cpu_env(), ["--seconds", "5"])
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    window = line["window"]
    assert window["pool_ran_dry"] is True
    assert window["requests"] == window["pool_requests"] == 5
    assert window["traced_requests"] >= 1
    assert 0 < window["trace_from_s"] < window["seconds"] < 5
    assert line["correct"] is True and line["failed"] == 0
    stderr = done.stderr.strip().splitlines()
    assert any(s.startswith("pool ran dry") for s in stderr)
    assert stderr[-1] == "correct True"
    with open(os.path.join(ROOT, ".bench_work", cell, "report.json")) as f:
        report = json.load(f)
    assert [m["word"] for m in report["trace_marks"]] == ["start", "stop"]
    assert report["requests"][-1]["traced"]


@pytest.mark.parametrize(
    "marks,traced,said",
    [
        ([], False, "no start mark, no stop mark, no traced request"),
        (["start"], True, "no stop mark"),
        (["start", "stop"], False, "no traced request"),
    ],
)
def test_a_traced_run_without_a_traced_span_prints_no_line(
    monkeypatch, capsys, marks, traced, said
):
    sys.path.insert(0, BENCH_DIR)
    import run as bench_run

    report = {
        "loop": "closed",
        "window": {"t_start": 100.0, "t_end": 125.03, "seconds": 30.0},
        "requests": [{"traced": traced}] * 188,
        "pool_requests": 188,
        "trace_marks": [{"word": w, "client_pc": 120.0} for w in marks],
    }
    monkeypatch.setattr(bench_run, "run_cell", lambda args: report)
    with pytest.raises(SystemExit) as e:
        bench_run.main([
            "--workload", "rehearsal.catchup", "--seed", "1", "--seconds",
            "30", "--trace", "1", "--benchmark-file", REHEARSAL,
        ])
    sentence = str(e.value.code)
    assert said + ":" in sentence
    for part in ("188 requests", "pool_requests 188",
                 "window.seconds 25.030 of 30.0", "trace_seconds 1"):
        assert part in sentence
    assert capsys.readouterr().out == ""


def test_no_chip_no_result():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    done = run("rehearsal.catchup", 0, env)
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_an_answer_altered_where_it_is_produced_is_not_correct(capsys):
    sys.path.insert(0, BENCH_DIR)
    import run as bench_run
    from harness import procs
    from harness.cell import Cell

    def broken(work, sock, wfd, trace):
        real = procs.service_command(work, sock, wfd, False)
        return [
            sys.executable,
            os.path.join(BENCH_DIR, "tests", "broken_service.py"), ROOT,
        ] + real[4:]

    os.environ["JAX_PLATFORMS"] = "cpu"
    args = argparse.Namespace(
        workload="rehearsal.catchup", seed=77, seconds=1.0, trace=0,
        control_guarantee="", rate=0.0, sweep="", benchmark_file=REHEARSAL,
    )
    args.cell = Cell(args.workload, REHEARSAL)
    report = bench_run.run_cell(args, service_command=broken)
    numbers = report["numbers"]
    assert numbers["rows_wrong"] >= len(report["requests"])
    assert numbers["requests_failed"] == 0
