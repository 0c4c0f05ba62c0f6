"""`readers/span_field_ratio.py` on a hand-made ring, and the two
metrics of PR 27 naming what they read."""

import pytest

from harness.cell import metric_reader
from readers import ledger_ratio, span_field_ratio

SPEC = {
    "span": "crypto.table_lookup", "numerator": "resident",
    "denominator": "n", "scale": 100,
}
WINDOW = {"wall_start": 1000.0, "t_start": 50.0, "t_end": 60.0}


def span(name, wall_s, **fields):
    out = {"name": name, "t0_wall_ns": wall_s * 1e9, "dur": 0.001}
    if fields:
        out["fields"] = fields
    return out


def test_ratio_of_the_fields_over_the_spans_that_start_in_the_window():
    ctx = {
        "window": WINDOW,
        "spans": [
            # a warm-up round that built its tables
            span("crypto.table_lookup", 999.0, n=128, resident=0, built=128),
            span("crypto.table_lookup", 1000.5, n=128, resident=128),
            span("crypto.table_lookup", 1004.0, n=10000, resident=9744),
            span("crypto.device_execute", 1004.1, n=999, resident=0),
            span("crypto.table_lookup", 1010.5, n=128, resident=0),  # after
        ],
    }
    assert span_field_ratio.read(ctx, SPEC) == pytest.approx(
        100 * (128 + 9744) / (128 + 10000)
    )
    every = dict(ctx, spans=ctx["spans"][1:2])
    assert span_field_ratio.read(every, SPEC) == 100


def test_left_out_where_no_span_carries_the_field():
    # the parent's lookups say n, tier and built, and no more
    parent = {
        "window": WINDOW,
        "spans": [
            span("crypto.table_lookup", 1001.0, n=128, tier="small", built=0),
            span("crypto.table_lookup", 1002.0),
        ],
    }
    assert span_field_ratio.read(parent, SPEC) is None
    assert span_field_ratio.read(dict(parent, spans=[]), SPEC) is None
    # rounds of no rows: a share of nothing is left out, not 0
    none = dict(parent, spans=[
        span("crypto.table_lookup", 1001.0, n=0, resident=0)
    ])
    assert span_field_ratio.read(none, SPEC) is None


def test_the_new_metrics_name_what_they_read():
    read, spec = metric_reader("table_resident_pct.live")
    assert read is span_field_ratio.read
    assert {k: spec[k] for k in SPEC} == SPEC
    read, spec = metric_reader("slices_per_round.live")
    assert read is ledger_ratio.read
    assert (spec["numerator"], spec["denominator"], spec["scale"]) == (
        "submissions", "rounds", 1
    )
