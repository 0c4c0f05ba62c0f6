"""Device-cost report: per-class accelerator cost tables + the
requests-per-dispatch amortization curve.

Reads any surface the device-cost ledger (obs/ledger.py) lands on:

- a `dump_dispatch_ledger` RPC response (raw or `{"result": ...}`
  envelope) pulled from a live node,
- a verify-service dump (the standalone service's own
  /dump_dispatch_ledger or STATS frame, PR 13): same shape plus a
  `per_client` tenant table — the multi-tenant device bill with real
  tenants, rendered as per-client submission/row counts next to the
  per-class cost shares,
- a document carrying the summary as a `device_cost` block,
- a bare `device_cost`/summary dict,

and renders the questions the ledger exists to answer: which submitter
class spent which device milliseconds (and what share), at what fill
efficiency (p50/p95 of per-round rows-requested / rows-dispatched),
with how much padding waste, and how many submissions each dispatch
amortized — per padded-bucket size, so the amortization curve shows
where cross-subsystem coalescing actually pays and where mesh_min_rows
or the ladder is mispriced.

Usage:
    curl -s localhost:26657/dump_dispatch_ledger | python tools/device_report.py -
    python tools/device_report.py dump.json [more.json ...] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(path: str) -> dict:
    if path == "-":
        return json.load(sys.stdin)
    with open(path) as f:
        return json.load(f)


def extract_summary(doc: dict) -> dict:
    """The device_cost/summary block from any supported shape; raises
    ValueError when the document carries none."""
    if not isinstance(doc, dict):
        raise ValueError("not a JSON object")
    if "result" in doc and isinstance(doc["result"], dict):
        doc = doc["result"]  # JSON-RPC envelope
    for key in ("summary", "device_cost"):
        block = doc.get(key)
        if isinstance(block, dict) and "rounds" in block:
            # a verify-service dump carries the tenant table BESIDE the
            # summary; attach it so the report can render the bill
            if isinstance(doc.get("per_client"), dict):
                block = dict(block)
                block["per_client"] = doc["per_client"]
            return block
    if "rounds" in doc and "per_class" in doc:
        return doc  # already a bare summary
    raise ValueError(
        "no device-cost block found (expected a dump_dispatch_ledger "
        "response, a document with 'device_cost', or a bare "
        "summary)"
    )


def _fmt_s(v: float) -> str:
    return f"{v * 1e3:,.1f} ms" if v < 1.0 else f"{v:,.2f} s"


def report_text(summary: dict, name: str = "") -> str:
    lines = []
    title = "device-cost ledger"
    if name:
        title += f": {name}"
    lines.append(f"== {title} ==")
    rounds = summary.get("rounds", 0)
    if not rounds:
        lines.append("(no scheduler rounds recorded)")
        return "\n".join(lines)
    lines.append(
        f"rounds {rounds} (fn {summary.get('fn_rounds', 0)}, sharded "
        f"{summary.get('sharded_rounds', 0)})   device time "
        f"{_fmt_s(summary.get('device_seconds', 0.0))}   queue wait "
        f"{_fmt_s(summary.get('queue_wait_seconds', 0.0))}   host prep "
        f"{_fmt_s(summary.get('host_prep_seconds', 0.0))}"
    )
    disp = summary.get("rows_dispatched", 0)
    pad = summary.get("padding_rows", 0)
    lines.append(
        f"rows {summary.get('rows_requested', 0)} requested -> {disp} "
        f"dispatched   padding {pad} rows"
        + (f" ({pad / disp:.1%} of dispatched)" if disp else "")
        + f"   fill p50 {summary.get('fill_ratio_p50', 0.0)} "
        f"p95 {summary.get('fill_ratio_p95', 0.0)}"
    )
    lines.append(
        f"requests/dispatch {summary.get('requests_per_dispatch', 0.0)}"
    )
    if summary.get("fill_window_truncated"):
        lines.append(
            "(fill percentiles over retained ring entries only — older "
            "rounds aged out; totals above are exact)"
        )
    per_class = summary.get("per_class") or {}
    if per_class:
        lines.append("")
        lines.append(
            f"{'class':<12} {'rows':>10} {'device':>12} {'share':>7} "
            f"{'rounds':>7} {'subs':>7} {'queue wait':>12}"
        )
        for klass, acct in sorted(
            per_class.items(),
            key=lambda kv: -kv[1].get("device_seconds", 0.0),
        ):
            lines.append(
                f"{klass:<12} {acct.get('rows', 0):>10} "
                f"{_fmt_s(acct.get('device_seconds', 0.0)):>12} "
                f"{acct.get('device_share', 0.0):>6.1%} "
                f"{acct.get('rounds', 0):>7} "
                f"{acct.get('submissions', 0):>7} "
                f"{_fmt_s(acct.get('queue_wait_seconds', 0.0)):>12}"
            )
    per_engine = summary.get("per_engine") or {}
    if per_engine:
        lines.append("")
        lines.append(
            "per-engine (the honest requests/dispatch axis — fn rounds "
            "are one submission each by construction):"
        )
        lines.append(
            f"{'engine':<14} {'rounds':>7} {'rows':>10} {'disp':>10} "
            f"{'fill':>6} {'reqs/disp':>10} {'device':>12}"
        )
        for eng, acct in sorted(
            per_engine.items(),
            key=lambda kv: -kv[1].get("device_seconds", 0.0),
        ):
            lines.append(
                f"{eng:<14} {acct.get('rounds', 0):>7} "
                f"{acct.get('rows_requested', 0):>10} "
                f"{acct.get('rows_dispatched', 0):>10} "
                f"{acct.get('fill_ratio', 0.0):>6.2f} "
                f"{acct.get('requests_per_dispatch', 0.0):>10} "
                f"{_fmt_s(acct.get('device_seconds', 0.0)):>12}"
            )
    per_client = summary.get("per_client") or {}
    if per_client:
        total_rows = sum(
            c.get("rows", 0) + c.get("fn_items", 0)
            for c in per_client.values()
        )
        lines.append("")
        lines.append(
            f"tenants ({len(per_client)} clients over the service's "
            "life):"
        )
        lines.append(
            f"{'client':<12} {'subs':>7} {'rows':>10} {'fn subs':>8} "
            f"{'fn items':>9} {'row share':>10}"
        )
        for client, c in sorted(
            per_client.items(),
            key=lambda kv: -(
                kv[1].get("rows", 0) + kv[1].get("fn_items", 0)
            ),
        ):
            rows = c.get("rows", 0) + c.get("fn_items", 0)
            share = rows / total_rows if total_rows else 0.0
            lines.append(
                f"{client:<12} {c.get('submissions', 0):>7} "
                f"{c.get('rows', 0):>10} "
                f"{c.get('fn_submissions', 0):>8} "
                f"{c.get('fn_items', 0):>9} {share:>9.1%}"
            )
    by_bucket = summary.get("by_bucket") or {}
    if by_bucket:
        lines.append("")
        lines.append("amortization curve (per padded bucket):")
        lines.append(
            f"{'bucket':>8} {'rounds':>7} {'rows req':>10} {'subs':>7} "
            f"{'fill':>6} {'reqs/disp':>10}"
        )
        for bucket, b in sorted(
            by_bucket.items(), key=lambda kv: int(kv[0])
        ):
            bi = int(bucket)
            fill = b["rows_requested"] / (bi * b["rounds"]) if b[
                "rounds"
            ] else 0.0
            lines.append(
                f"{bi:>8} {b['rounds']:>7} {b['rows_requested']:>10} "
                f"{b['submissions']:>7} {fill:>6.2f} "
                f"{b['submissions'] / b['rounds']:>10.2f}"
            )
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(
        description="per-class device-cost tables + amortization curve "
        "from dump_dispatch_ledger dumps"
    )
    ap.add_argument(
        "paths", nargs="+",
        help="dump JSON files ('-' = stdin)",
    )
    ap.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit the extracted summaries as JSON instead of tables",
    )
    args = ap.parse_args()
    out = {}
    rc = 0
    for path in args.paths:
        name = os.path.basename(path) if path != "-" else "stdin"
        try:
            summary = extract_summary(_load(path))
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"# {name}: {e}", file=sys.stderr)
            rc = 1
            continue
        out[name] = summary
        if not args.as_json:
            print(report_text(summary, name=name))
            print()
    if args.as_json:
        print(json.dumps(out, indent=1))
    return rc if out else 1


if __name__ == "__main__":
    raise SystemExit(main())
