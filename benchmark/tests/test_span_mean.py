"""`readers/span_mean.py` on a hand-made ring, and the rehearsal cells
printing the per-layer metrics that read the program's own spans."""

import json

import pytest

from harness.cell import metric_reader
from readers import span_mean
from test_end_to_end import cpu_env, run

KINDS = {
    "client_encode_ms": "verify.client_encode",
    "wire_in_ms": "verify.wire_in",
    "frame_decode_ms": "verify.frame_decode",
    "table_lookup_ms": "crypto.table_lookup",
    "device_call_ms": "crypto.device_execute",
}


def span(name, wall_s, dur):
    return {"name": name, "t0_wall_ns": wall_s * 1e9, "dur": dur}


def test_mean_of_the_named_spans_that_start_in_the_window():
    ctx = {
        "window": {"wall_start": 1000.0, "t_start": 50.0, "t_end": 60.0},
        "spans": [
            span("verify.wire_in", 999.9, 9.0),  # a warm-up request
            span("verify.wire_in", 1000.0, 0.002),
            span("verify.frame_decode", 1001.0, 7.0),  # another name
            span("verify.wire_in", 1009.9, 0.004),
            span("verify.wire_in", 1010.1, 9.0),  # after the window
        ],
    }
    assert span_mean.read(ctx, {"span": "verify.wire_in"}) == (
        pytest.approx(3.0)
    )
    assert span_mean.read(ctx, {"span": "verify.frame_decode"}) == (
        pytest.approx(7000.0)
    )
    # a program that records no such span: left out, not 0
    assert span_mean.read(ctx, {"span": "crypto.table_lookup"}) is None
    empty = dict(ctx, spans=[])
    assert span_mean.read(empty, {"span": "verify.wire_in"}) is None


@pytest.mark.parametrize("kind", ["catchup", "live"])
def test_each_metric_names_its_span(kind):
    for base, name in KINDS.items():
        read, spec = metric_reader(f"{base}.{kind}")
        assert read is span_mean.read and spec["span"] == name


@pytest.mark.parametrize("kind", ["catchup", "live"])
def test_rehearsal_cell_prints_the_span_metrics_of_its_kind(kind):
    done = run(f"rehearsal.{kind}", 1, cpu_env())
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    for base in KINDS:
        assert metrics[f"{base}.{kind}"]["unit"] == "ms"
        assert metrics[f"{base}.{kind}"]["value"] > 0
