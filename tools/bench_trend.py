"""Bench-trajectory trend + regression gate over BENCH_r*/MULTICHIP_r*.

Every PR leaves a BENCH_rNN.json (and sometimes MULTICHIP_rNN.json)
artifact, but nothing compared them across runs: the r04–r06 "silent
perf regression" (device rows quietly replaced by CPU-fallback rows
30x below them) was only found by archaeology. This tool makes the
trajectory first-class:

- **Ingest** every artifact round, normalizing the three historical
  shapes (wrapped `{parsed: ...}` rows from r01–r04, direct metric
  dicts from r05+, structured backend-mismatch failures from r07+) into
  flat rows; failure artifacts are recorded as skips, never as values.

- **Backend partition**: rows group by (family, metric, backend,
  device_count) and are ONLY ever compared within a group. Backend
  comes from the `meta` stamp (`platform`; `backend` in artifacts older
  than the stamp's current form); pre-meta artifacts fall back to the
  top-level `backend` field, then to "cpu" — the honest default, since
  every unlabeled row still checked in WAS a CPU row. A CPU row can
  therefore never flag against a chip capture, and a chip recapture
  never "improves on" CPU numbers.

- **Gate** (`--check`): exit non-zero when any tier-1 family's
  HEADLINE metric (the artifact's top-level row) regressed more than
  `--threshold` (15% default) against the best-known value on the same
  backend/device-count. Regressions in `extra_metrics` rows are
  reported as warnings (they fail only under `--strict`) — the
  checked-in history contains honest host-noise swings there
  (e.g. ed25519_commit10k_latency r05→r06: +26% on an unrelated-PR
  rerun), and a gate that cries wolf gets deleted.

- **Conservation** (PR 15): artifacts carrying a `wall_conservation`
  block are schema-validated — buckets must sum to the measured wall
  per height (obs.report.check_conservation) or the artifact's rows
  are rejected outright — and `--check` additionally fails when the
  LATEST artifact's aggregate dark_time fraction exceeds
  `--dark-threshold` (0.05 default): wall time with no instrumented
  owner is a regression in the attribution plane itself.

- **Render**: TREND.md (per-family tables: best/latest/delta with the
  round each came from) + machine-readable TREND.json.

Usage:
    python tools/bench_trend.py                       # print TREND.md
    python tools/bench_trend.py --write               # write TREND.{md,json}
    python tools/bench_trend.py --check               # CI gate
    python tools/bench_trend.py --check extra_r99.json  # + synthetic rows
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# stdlib-only import (obs/ carries no deps): the one conservation-check
# implementation bench.py stamps with and this gate validates with —
# a local copy would drift from the bucket list
from tendermint_tpu.obs.report import check_conservation  # noqa: E402

# --- metric classification --------------------------------------------------

# ordered prefix -> family (first match wins; longer prefixes first)
_FAMILY_PREFIXES = (
    ("verify_service", "verify_service"),
    ("scheduler_", "scheduler"),
    ("consensus_pipeline", "consensus_pipeline"),
    ("consensus_pacing", "consensus_pacing"),
    ("consensus_", "consensus"),
    ("lightserve", "lightserve"),
    ("light_", "light"),
    ("committee", "committee_scale"),
    ("sequencer", "sequencer_stream"),
    ("commit_", "commit_path"),
    ("wal_", "commit_path"),
    # QC round compression (PR 14): headline blocksync_commits_per_s@N
    # must classify under qc_catchup, so this prefix outranks the plain
    # blocksync family below
    ("blocksync_commits_per_s", "qc_catchup"),
    ("qc_", "qc_catchup"),
    ("blocksync", "blocksync"),
    ("quorum_", "consensus"),
    ("vote_latency", "crypto"),
    ("ed25519", "crypto"),
    ("bls_", "crypto"),
    ("sr25519", "crypto"),
    ("secp256k1", "crypto"),
    ("sha256", "crypto"),
    ("multichip", "multichip"),
)

# families whose headline rows gate CI (--check); the rest are
# informational trend lines
TIER1_FAMILIES = frozenset(
    {
        "crypto",
        # QC-chained height pipelining (PERF_ANALYSIS §22): headline is
        # effective wall-per-height with overlapped consecutive heights;
        # its conservation block books buckets > wall only by the
        # explicit pipeline_overlap_ms credit (obs.check_conservation)
        "consensus_pipeline",
        "consensus_pacing",
        "consensus",
        "lightserve",
        "light",
        "committee_scale",
        "sequencer_stream",
        # the split-brain verify plane (PR 13): headline is
        # wall-per-height at 32 validators with real crypto over IPC
        "verify_service",
        # QC round compression (PR 14): headline is
        # blocksync_commits_per_s@100 (direction higher) — a QC
        # regression gates like every other plane
        "qc_catchup",
        "commit_path",
        "blocksync",
        "multichip",
        # the device_cost fill/padding rows (never headline, so this
        # only makes them warn-level / --strict-promotable instead of
        # purely informational)
        "scheduler",
    }
)

# metric-name tokens that mean lower-is-better; everything else
# defaults to higher-is-better (throughputs, rates, reductions)
_LOWER_TOKENS = (
    "latency",
    "_ms",
    "wall",
    "_lag",
    "fsync",
    "floor_share",
    "wait",
    "critical_path",
    "_ticks",
    "per_key",
    "encodes_per",
    "_behind",
)

# oddballs the token heuristic can't classify from the name alone
_DIRECTION_OVERRIDES = {
    "bls_aggregate_verify_1k": "lower",  # ms for a 1k-signer aggregate
    "light_bisection_1k": "higher",  # sigs/s on the 1k-validator chain
    # padding fraction of dispatched rows (device_cost block): waste
    "scheduler_padding_fraction": "lower",
}


def family_of(metric: str) -> str:
    for prefix, fam in _FAMILY_PREFIXES:
        if metric.startswith(prefix):
            return fam
    return metric.split("_", 1)[0] or "other"


def direction_of(metric: str, unit: str = "") -> str:
    """'higher' or 'lower' (is better)."""
    ov = _DIRECTION_OVERRIDES.get(metric)
    if ov:
        return ov
    if any(tok in metric for tok in _LOWER_TOKENS):
        return "lower"
    u = (unit or "").strip().lower()
    if u.startswith("ms") or u == "s" or u.startswith("s for"):
        return "lower"
    return "higher"


# --- artifact normalization -------------------------------------------------

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def _round_of(path: str, fallback: int) -> int:
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else fallback


def _meta_platform(meta) -> str:
    """The platform a `meta` stamp names ('' when it names none): the
    `platform` key, or `backend` in artifacts older than that key."""
    if not isinstance(meta, dict):
        return ""
    return str(meta.get("platform") or meta.get("backend") or "")


def _infer_backend(doc: dict, payload: dict) -> str:
    """meta stamp > explicit backend field > 'cpu' (the honest default:
    every unlabeled row still checked in was a CPU row)."""
    for d in (payload, doc):
        platform = _meta_platform(d.get("meta"))
        if platform:
            return platform
    for d in (payload, doc):
        b = d.get("backend")
        if isinstance(b, str) and b:
            return b
    return "cpu"


def _device_count(doc: dict, payload: dict) -> int:
    for d in (payload, doc):
        meta = d.get("meta")
        if isinstance(meta, dict) and meta.get("device_count"):
            return int(meta["device_count"])
    if doc.get("n_devices"):
        return int(doc["n_devices"])
    return 1


def _ledger_rows(payload: dict) -> list[dict]:
    """Synthesized extra-metric rows from a PR 12 `device_cost` block:
    fill-efficiency percentiles + the padding fraction, warn-level like
    every other extra metric (`--strict` promotes). Only emitted when
    the family actually drove scheduler rounds — a zero-round block
    would land fill 0.0 and cry regression forever."""
    dc = payload.get("device_cost")
    if not isinstance(dc, dict):
        return []
    # guard on SIG rounds: fn-lane rounds carry no bucket fill, so a
    # span of only fn rounds would stamp fill 0.0 and cry regression
    # against any prior real fill forever
    if not (dc.get("rounds", 0) - dc.get("fn_rounds", 0)):
        return []
    rows = [
        {
            "metric": "scheduler_fill_ratio_p50",
            "value": dc.get("fill_ratio_p50"),
            "unit": "rows-requested/rows-dispatched per round, p50",
        },
        {
            "metric": "scheduler_fill_ratio_p95",
            "value": dc.get("fill_ratio_p95"),
            "unit": "rows-requested/rows-dispatched per round, p95",
        },
    ]
    disp = dc.get("rows_dispatched") or 0
    if disp:
        rows.append(
            {
                "metric": "scheduler_padding_fraction",
                "value": round(dc.get("padding_rows", 0) / disp, 4),
                "unit": "padding rows / dispatched rows",
            }
        )
    return [r for r in rows if r["value"] is not None]


def _metric_rows(payload: dict) -> list[tuple[dict, bool]]:
    """(row_dict, is_headline) pairs from one normalized payload."""
    rows = []
    if payload.get("metric") is not None and payload.get("value") is not None:
        rows.append((payload, True))
    for e in (payload.get("extra_metrics") or []) + _ledger_rows(payload):
        if (
            isinstance(e, dict)
            and e.get("metric") is not None
            and e.get("value") is not None
        ):
            rows.append((e, False))
    # multichip per-device-count series (PR 6 capture format)
    for e in payload.get("series") or []:
        if (
            isinstance(e, dict)
            and e.get("metric") is not None
            and e.get("value") is not None
        ):
            rows.append((e, True))
    return rows


def _conservation_of(payload: dict, name: str):
    """(dark_row, violation) from a payload's `wall_conservation`
    block (PR 15). Artifacts without the block — everything before
    r14 — return (None, None): the audit is only enforced where the
    bench claimed to have run it. A block whose buckets do NOT sum to
    the measured wall is a schema violation: the artifact's rows are
    rejected outright (a row whose own attribution doesn't reconcile
    cannot be trusted as a measurement)."""
    block = payload.get("wall_conservation")
    if block is None:
        return None, None
    errs = check_conservation(block)
    if errs:
        return None, f"conservation violation: {'; '.join(errs[:3])}"
    agg = (block.get("aggregate") or {}) if isinstance(block, dict) else {}
    if not agg:
        return None, None
    return (
        {
            "file": name,
            "dark_fraction": float(agg.get("dark_fraction", 0.0)),
            "dark_fraction_max": float(
                agg.get("dark_fraction_max", 0.0)
            ),
            "n_heights": int(agg.get("n_heights", 0)),
        },
        None,
    )


def ingest(
    paths: list[str],
) -> tuple[list[dict], list[dict], list[dict]]:
    """Normalize artifacts into (rows, skipped, conservation)."""
    rows: list[dict] = []
    skipped: list[dict] = []
    conservation: list[dict] = []
    for i, path in enumerate(paths):
        name = os.path.basename(path)
        rnd = _round_of(path, fallback=1000 + i)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            skipped.append({"file": name, "reason": f"unreadable: {e}"})
            continue
        if not isinstance(doc, dict):
            skipped.append({"file": name, "reason": "not an object"})
            continue
        payload = doc
        if "parsed" in doc:  # r01–r04 wrapped shape
            payload = doc["parsed"]
            if not isinstance(payload, dict) or doc.get("rc"):
                skipped.append(
                    {
                        "file": name,
                        "reason": f"failed run (rc={doc.get('rc')})",
                    }
                )
                continue
        if payload.get("kind") == "backend_mismatch" or (
            payload.get("error") and payload.get("metric") is None
        ):
            skipped.append(
                {
                    "file": name,
                    "reason": (
                        f"structured failure: "
                        f"{payload.get('kind') or payload.get('error')}"
                    ),
                }
            )
            continue
        dark_row, violation = _conservation_of(payload, name)
        if violation:
            skipped.append({"file": name, "reason": violation})
            continue
        if dark_row is not None:
            dark_row["round"] = rnd
            conservation.append(dark_row)
        pairs = _metric_rows(payload)
        if not pairs:
            skipped.append(
                {"file": name, "reason": "no metric rows (dryrun/capture)"}
            )
            continue
        backend = _infer_backend(doc, payload)
        devices = _device_count(doc, payload)
        for row, headline in pairs:
            metric = str(row["metric"])
            try:
                value = float(row["value"])
            except (TypeError, ValueError):
                continue
            rows.append(
                {
                    "file": name,
                    "round": rnd,
                    "metric": metric,
                    "value": value,
                    "unit": row.get("unit", ""),
                    "family": family_of(metric),
                    "direction": direction_of(metric, row.get("unit", "")),
                    "backend": _meta_platform(row.get("meta")) or backend,
                    "devices": (
                        int(row["devices"])
                        if row.get("devices")
                        else devices
                    ),
                    "headline": headline,
                }
            )
    return rows, skipped, conservation


# --- trajectory + gate ------------------------------------------------------


def build_groups(rows: list[dict]) -> list[dict]:
    """Group rows by (family, metric, backend, devices); compute
    best-known / latest / regression."""
    by_key: dict[tuple, list[dict]] = {}
    for r in rows:
        by_key.setdefault(
            (r["family"], r["metric"], r["backend"], r["devices"]), []
        ).append(r)
    groups = []
    for (fam, metric, backend, devices), rs in sorted(by_key.items()):
        rs = sorted(rs, key=lambda r: r["round"])
        latest = rs[-1]
        direction = latest["direction"]
        if direction == "higher":
            best = max(rs, key=lambda r: r["value"])
            reg = (
                (best["value"] - latest["value"]) / best["value"]
                if best["value"]
                else 0.0
            )
        else:
            best = min(rs, key=lambda r: r["value"])
            reg = (
                (latest["value"] - best["value"]) / best["value"]
                if best["value"]
                else 0.0
            )
        groups.append(
            {
                "family": fam,
                "metric": metric,
                "backend": backend,
                "devices": devices,
                "direction": direction,
                "n_rows": len(rs),
                "best": best["value"],
                "best_round": best["round"],
                "latest": latest["value"],
                "latest_round": latest["round"],
                "headline": latest["headline"],
                # positive = latest is worse than best-known
                "regression": round(max(0.0, reg), 4),
            }
        )
    return groups


def check_gate(
    groups: list[dict], threshold: float, strict: bool = False
) -> tuple[list[dict], list[dict]]:
    """(failures, warnings): tier-1 headline regressions past the
    threshold fail; extra-metric regressions warn (fail iff strict)."""
    failures, warnings = [], []
    for g in groups:
        if g["regression"] <= threshold:
            continue
        if g["n_rows"] < 2:
            continue  # a single capture cannot regress against itself
        if g["family"] in TIER1_FAMILIES and g["headline"]:
            failures.append(g)
        elif g["family"] in TIER1_FAMILIES:
            (failures if strict else warnings).append(g)
    return failures, warnings


def check_dark(
    conservation: list[dict], threshold: float
) -> list[dict]:
    """Absolute dark-time gate: the LATEST round carrying a
    conservation block must keep its aggregate dark fraction under
    `threshold` — wall time with no instrumented owner is a regression
    in the attribution plane itself, regardless of how fast the run
    was. (Not a vs-best comparison: dark near zero is the steady state,
    and judging noise around zero in relative terms would cry wolf.)"""
    if not conservation:
        return []
    latest = max(conservation, key=lambda c: c["round"])
    if latest["dark_fraction"] > threshold:
        return [dict(latest, threshold=threshold)]
    return []


# --- rendering --------------------------------------------------------------


def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.4g}" if abs(v) < 1 else f"{v:,.1f}"


def render_md(
    groups: list[dict],
    skipped: list[dict],
    files: list[str],
    threshold: float,
) -> str:
    lines = [
        "# Bench trajectory (tools/bench_trend.py)",
        "",
        f"Ingested {len(files)} artifacts; rows compare ONLY within "
        "their (family, metric, backend, devices) group — CPU rows "
        "never judge TPU captures or vice versa. `Δbest` is how far "
        "the latest capture sits from the best-known on the same "
        f"backend (gate threshold {threshold:.0%} on tier-1 headline "
        "rows).",
        "",
    ]
    by_family: dict[str, list[dict]] = {}
    for g in groups:
        by_family.setdefault(g["family"], []).append(g)
    for fam in sorted(by_family):
        gs = by_family[fam]
        tier = "tier-1" if fam in TIER1_FAMILIES else "info"
        lines.append(f"## {fam} ({tier})")
        lines.append("")
        lines.append(
            "| metric | backend | dev | dir | best (r) | latest (r) "
            "| Δbest |"
        )
        lines.append("|---|---|---|---|---|---|---|")
        for g in gs:
            delta = (
                f"**-{g['regression']:.1%}**"
                if g["regression"] > threshold and g["n_rows"] > 1
                else (
                    f"-{g['regression']:.1%}"
                    if g["regression"] > 0
                    else "="
                )
            )
            mark = "" if g["headline"] else " *(extra)*"
            lines.append(
                f"| {g['metric']}{mark} | {g['backend']} | "
                f"{g['devices']} | {g['direction']} | "
                f"{_fmt(g['best'])} (r{g['best_round']:02d}) | "
                f"{_fmt(g['latest'])} (r{g['latest_round']:02d}) | "
                f"{delta} |"
            )
        lines.append("")
    if skipped:
        lines.append("## Skipped artifacts")
        lines.append("")
        for s in skipped:
            lines.append(f"- `{s['file']}`: {s['reason']}")
        lines.append("")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(
        description="bench-artifact trajectory + backend-partitioned "
        "regression gate"
    )
    ap.add_argument(
        "files",
        nargs="*",
        help="extra artifact files appended to the --dir scan "
        "(synthetic rows, out-of-tree captures)",
    )
    ap.add_argument(
        "--dir",
        default=REPO_ROOT,
        help="directory scanned for BENCH_r*.json / MULTICHIP_r*.json "
        "(default: repo root)",
    )
    ap.add_argument(
        "--no-scan",
        action="store_true",
        help="ingest ONLY the positional files (skip the --dir scan)",
    )
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.15,
        help="regression fraction that fails --check (default 0.15)",
    )
    ap.add_argument(
        "--check",
        action="store_true",
        help="exit non-zero on tier-1 headline regressions past the "
        "threshold",
    )
    ap.add_argument(
        "--strict",
        action="store_true",
        help="--check also fails on extra-metric regressions",
    )
    ap.add_argument(
        "--dark-threshold",
        type=float,
        default=0.05,
        help="max aggregate dark_time fraction the latest artifact's "
        "wall_conservation block may carry under --check "
        "(default 0.05)",
    )
    ap.add_argument(
        "--write",
        action="store_true",
        help="write TREND.md + TREND.json into --dir",
    )
    ap.add_argument("--json", action="store_true", help="print TREND.json")
    args = ap.parse_args()

    files: list[str] = []
    if not args.no_scan:
        files += sorted(glob.glob(os.path.join(args.dir, "BENCH_r*.json")))
        files += sorted(
            glob.glob(os.path.join(args.dir, "MULTICHIP_r*.json"))
        )
    files += args.files
    if not files:
        print("no artifacts found", file=sys.stderr)
        return 2

    rows, skipped, conservation = ingest(files)
    groups = build_groups(rows)
    failures, warnings = check_gate(
        groups, args.threshold, strict=args.strict
    )
    dark_failures = check_dark(conservation, args.dark_threshold)
    doc = {
        "schema": "tm-tpu/bench-trend/v1",
        "threshold": args.threshold,
        "files": [os.path.basename(f) for f in files],
        "rows": rows,
        "groups": groups,
        "skipped": skipped,
        "conservation": {
            "dark_threshold": args.dark_threshold,
            "blocks": conservation,
            "failures": dark_failures,
        },
        "check": {
            "failures": failures,
            "warnings": warnings,
            "dark_failures": dark_failures,
            "ok": not failures and not dark_failures,
        },
    }
    md = render_md(groups, skipped, files, args.threshold)

    if args.write:
        with open(os.path.join(args.dir, "TREND.md"), "w") as f:
            f.write(md + "\n")
        with open(os.path.join(args.dir, "TREND.json"), "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(
            f"wrote {os.path.join(args.dir, 'TREND.md')} and TREND.json",
            file=sys.stderr,
        )
    elif args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        print(md)

    for w in warnings:
        print(
            f"# WARN extra-metric regression: {w['metric']} "
            f"[{w['backend']} x{w['devices']}] best {_fmt(w['best'])} "
            f"(r{w['best_round']:02d}) -> latest {_fmt(w['latest'])} "
            f"(r{w['latest_round']:02d}), -{w['regression']:.1%}",
            file=sys.stderr,
        )
    if args.check:
        if dark_failures:
            for d in dark_failures:
                print(
                    f"# FAIL dark-time gate: {d['file']} "
                    f"dark_fraction {d['dark_fraction']:.3f} > "
                    f"{args.dark_threshold:.3f} over {d['n_heights']} "
                    f"heights (worst height "
                    f"{d['dark_fraction_max']:.3f}) — wall time with "
                    "no instrumented owner",
                    file=sys.stderr,
                )
            if not failures:
                return 1
        if failures:
            for g in failures:
                print(
                    f"# FAIL tier-1 regression: {g['metric']} "
                    f"[{g['backend']} x{g['devices']}] best "
                    f"{_fmt(g['best'])} (r{g['best_round']:02d}) -> "
                    f"latest {_fmt(g['latest'])} "
                    f"(r{g['latest_round']:02d}), -{g['regression']:.1%} "
                    f"> {args.threshold:.0%}",
                    file=sys.stderr,
                )
            return 1
        print(
            f"# bench-trend check ok: {len(groups)} metric groups, "
            f"{len(warnings)} extra-metric warnings, 0 tier-1 headline "
            f"regressions",
            file=sys.stderr,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
