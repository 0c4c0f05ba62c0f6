"""`readers/span_total_per_request.py` on a hand-made ring, the metrics
that read the service's newer spans naming what they read, and a
rehearsal catch-up cell printing the two of them that every traced
catch-up window records."""

import json

import pytest

from harness.cell import metric_reader
from readers import span_mean, span_total_per_request
from test_end_to_end import cpu_env, run

WINDOW = {"wall_start": 1000.0, "t_start": 50.0, "t_end": 60.0}


def span(name, wall_s, dur):
    return {"name": name, "t0_wall_ns": wall_s * 1e9, "dur": dur}


def request(failed=False):
    return {"failed": failed}


def test_total_of_the_named_spans_in_the_window_per_request_served():
    ctx = {
        "window": WINDOW,
        "requests": [request(), request(), request(True), request()],
        "spans": [
            span("runtime.gc", 999.9, 9.0),  # warm-up
            span("runtime.gc", 1000.0, 0.002),
            span("runtime.gc", 1004.0, 0.001),
            span("verify.wire_out", 1004.5, 7.0),  # another name
            span("runtime.gc", 1010.0, 0.003),
            span("runtime.gc", 1010.1, 9.0),  # after the window
        ],
    }
    spec = {"span": "runtime.gc"}
    # 6 ms over the three requests that did not fail
    assert span_total_per_request.read(ctx, spec) == pytest.approx(2.0)


@pytest.mark.parametrize(
    "spans, requests",
    [
        ([], [request()]),  # a program that records no such span
        ([span("runtime.gc", 999.0, 0.5)], [request()]),  # none inside
        ([span("runtime.gc", 1001.0, 0.5)], [request(True)]),  # none served
        ([span("runtime.gc", 1001.0, 0.5)], []),
    ],
    ids=["no-span", "outside", "all-failed", "no-request"],
)
def test_left_out_not_zero(spans, requests):
    ctx = {"window": WINDOW, "requests": requests, "spans": spans}
    assert span_total_per_request.read(ctx, {"span": "runtime.gc"}) is None


@pytest.mark.parametrize(
    "name, reader, span_name",
    [
        ("gather_ms.catchup", span_mean, "verify.client_gather"),
        ("wire_out_ms.catchup", span_mean, "verify.wire_out"),
        ("wire_out_ms.live", span_mean, "verify.wire_out"),
        ("wire_out_ms.live50", span_mean, "verify.wire_out"),
        ("gc_ms.catchup", span_total_per_request, "runtime.gc"),
        ("gc_ms.live", span_total_per_request, "runtime.gc"),
        ("gc_ms.live50", span_total_per_request, "runtime.gc"),
        ("ed_prep_ms.mixed", span_mean, "crypto.ed_prep"),
    ],
)
def test_each_metric_names_its_span(name, reader, span_name):
    read, spec = metric_reader(name)
    assert read is reader.read and spec["span"] == span_name


def test_rehearsal_catchup_prints_the_gather_and_the_way_back():
    done = run("rehearsal.catchup", 1, cpu_env())
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    for name in ("gather_ms.catchup", "wire_out_ms.catchup"):
        assert metrics[name]["unit"] == "ms"
        assert metrics[name]["value"] > 0
