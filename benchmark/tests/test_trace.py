"""The reduction from a trace to busy time, gaps and program time."""

import os

import pytest

from harness import trace

# two `small@8` rounds of the rehearsal cell on a TPU v5 lite (chip run,
# PR 24), cut to the device plane's `XLA Ops` and `XLA Modules` lines
RECORDED = os.path.join(
    os.path.dirname(__file__), "data", "tpu.xplane.pb.xz"
)


def test_union_counts_nested_and_overlapping_events_once():
    merged = trace.merge([(0, 10), (2, 5), (8, 14), (20, 30)])
    assert merged == [[0, 14], [20, 30]]
    assert trace.gaps(merged) == [(14, 20)]


def test_reduce_on_a_made_up_plane():
    plane = {
        "name": "/device:TPU:0",
        "ops": [(0, 4_000_000_000, "while"), (1_000_000_000, 2_000_000_000,
                "fusion.1"), (6_000_000_000, 7_000_000_000, "fusion.1")],
        "modules": [(0, 4_000_000_000, "jit__verify_cached_big(123)"),
                    (6_000_000_000, 7_000_000_000, "jit_scatter(9)")],
    }
    out = trace.reduce([plane])
    assert out["busy_s"] == 5.0 and out["chips"] == 1
    assert out["gaps"] == [(4_000_000_000, 6_000_000_000)]
    assert out["modules"]["jit__verify_cached_big"] == [1, 4.0]
    assert out["ops"]["fusion.1"] == 2.0
    assert trace.reduce([{"name": "x", "ops": [], "modules": []}]) is None
    spans = [(3_900_000_000, 5_500_000_000, "scheduler.host_prep"),
             (3_000_000_000, 5_600_000_000, "verify.queue")]
    assert trace.label_gaps(out["gaps"], spans) == [
        ["scheduler.host_prep", 2.0]
    ]
    assert trace.label_gaps(out["gaps"], spans[1:]) == [
        ["in the service, no span", 2.0]
    ]
    assert trace.label_gaps(out["gaps"], [(0, 4_400_000_000, "x")]) == [
        ["no request in the service", 2.0]
    ]
    assert trace.label_gaps(out["gaps"], []) == [
        ["no request in the service", 2.0]
    ]
    assert trace.op_name("%fusion.7 = u8[4]{0} fusion(u8[4]{0} %p)") == "fusion.7"
    assert out["module_starts"][0] == (0, "jit__verify_cached_big")
    # as many host spans as programs: the median difference; else the mark
    assert trace.zero_wall_ns(out["module_starts"], [1005, 7_000_001_003], 9) in (
        1005, 1_000_001_003
    )
    assert trace.zero_wall_ns(out["module_starts"], [1005], 9) == 9


def test_reduce_on_the_recorded_trace():
    planes = trace.read_planes(RECORDED)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert len(planes[0]["ops"]) == 113802
    out = trace.reduce(planes)
    assert out["chips"] == 1
    assert out["busy_s"] == pytest.approx(0.031093791, rel=1e-6)
    assert list(out["modules"]) == ["jit__verify_cached_small"]
    count, seconds = out["modules"]["jit__verify_cached_small"]
    assert count == 2 and seconds == pytest.approx(0.031126546, rel=1e-6)
    # the ops of a program lie inside its module events: busy <= modules
    assert out["busy_s"] <= seconds
    first = min(s for s, _, _ in planes[0]["ops"])
    last = max(e for _, e, _ in planes[0]["ops"])
    idle = sum(b - a for a, b in out["gaps"]) / 1e9
    assert idle == pytest.approx((last - first) / 1e9 - out["busy_s"])
    # the long gap is the wait between the two requests, 5 a second
    assert out["gaps"][0][1] - out["gaps"][0][0] == pytest.approx(
        0.223e9, rel=0.01
    )
    assert max(out["ops"], key=out["ops"].get).startswith("while.")
