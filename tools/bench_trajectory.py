#!/usr/bin/env python
"""Perf-trajectory gate over the banked benchmark rounds.

Folds the repo's banked ``BENCH_r*.json`` / ``SERVE_r*.json`` result
files into one longitudinal report per metric series, with the same
trailing-median regression detection the step-history tracker applies to
production saves (``telemetry/history.py``): a round whose headline
throughput drops below ``1/factor`` of the trailing-window median is
flagged — and, with ``--fail-on-regression``, fails the gate.  Wired
into ``tools/check.sh`` so a PR that tanks a banked number is caught by
CI, not by the next human reading the JSONs.

Robustness over the real (messy) bank:

- rounds come in two shapes — the raw bench line (``{"metric": ...}``)
  and the driver wrapper (``{"parsed": {...}, "tail": "..."}``); when
  ``parsed`` is null the result line is recovered from the tail;
- rounds are grouped into series by (metric, backend) — a TPU
  0.02 GB/s round must not read as a regression of a CPU series;
- rounds marked ``aux.incomplete`` are listed but excluded from both
  baselines and verdicts (a watchdog-killed partial is not a datapoint);
- verdicts need ``history.MIN_BASELINE_ENTRIES`` complete prior rounds,
  exactly like production regression detection.

Usage: tools/bench_trajectory.py [root] [--json] [--fail-on-regression]
       [--factor F] [--window N]
Exit codes: 0 clean, 1 regression (with --fail-on-regression), 2 usage.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchsnapshot_tpu import knobs  # noqa: E402
from torchsnapshot_tpu.telemetry import history  # noqa: E402

_ROUND_RE = re.compile(r"^(?P<prefix>[A-Z]+)_r(?P<round>\d+)\.json$")
_SERIES_PREFIXES = ("BENCH", "SERVE")


def _recover_from_tail(tail: str) -> Optional[Dict[str, Any]]:
    """The bench prints ONE result JSON line on stdout; a driver that
    failed to parse it (interleaved logs) still banked the raw tail."""
    for line in reversed((tail or "").strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            doc = json.loads(line)
        except (json.JSONDecodeError, ValueError):
            continue
        if isinstance(doc, dict) and "metric" in doc:
            return doc
    return None


def load_round(path: str) -> Optional[Dict[str, Any]]:
    """The bench result dict inside one banked round file, or None."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None
    if not isinstance(doc, dict):
        return None
    if "metric" in doc:
        return doc
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        return parsed
    return _recover_from_tail(doc.get("tail") or "")


def _normalize_backend(backend: Optional[str]) -> str:
    backend = (backend or "unknown").lower()
    return "cpu" if backend == "cpu_fallback" else backend


def collect_rounds(root: str) -> List[Dict[str, Any]]:
    """Every banked round under ``root``, as flat records:
    ``{series, round, value, unit, incomplete, file}`` — one record for
    the headline metric, plus one for the serve probe's warm aggregate
    when present (the serving tier's own trajectory)."""
    records: List[Dict[str, Any]] = []
    for path in sorted(glob.glob(os.path.join(root, "*_r*.json"))):
        m = _ROUND_RE.match(os.path.basename(path))
        if m is None or m.group("prefix") not in _SERIES_PREFIXES:
            continue
        rnd = int(m.group("round"))
        # Series are namespaced by bank prefix: SERVE_r01's headline save
        # number must not interleave into the BENCH series' round axis.
        bank = m.group("prefix").lower()
        doc = load_round(path)
        fname = os.path.basename(path)
        if doc is None:
            records.append(
                {
                    "series": f"{bank}:unparseable",
                    "round": rnd,
                    "value": None,
                    "unit": None,
                    "incomplete": True,
                    "file": fname,
                }
            )
            continue
        aux = doc.get("aux") or {}
        backend = _normalize_backend(doc.get("backend"))
        incomplete = bool(aux.get("incomplete"))
        value = doc.get("value")
        records.append(
            {
                "series": f"{bank}:{doc.get('metric', 'unknown')}:{backend}",
                "round": rnd,
                "value": float(value) if isinstance(value, (int, float)) else None,
                "unit": doc.get("unit"),
                "incomplete": incomplete,
                "file": fname,
            }
        )
        serve = aux.get("serve_probe") or {}
        warm = (serve.get("warm") or {}).get("aggregate_gbps")
        if isinstance(warm, (int, float)):
            records.append(
                {
                    "series": f"serve_warm_aggregate:{backend}",
                    "round": rnd,
                    "value": float(warm),
                    "unit": "GB/s",
                    "incomplete": incomplete,
                    "file": fname,
                }
            )
        # Multi-host peer-serving aggregate: the --serve probe's round-3
        # fleet bandwidth (H hosts pulling peer-first from seeded
        # daemons).  Its own gated series so a change that silently
        # drops the peer tier back to per-host origin pulls — same
        # correctness, none of the fan-out — fails the gate.
        mh_agg = (serve.get("multihost") or {}).get("aggregate_gbps")
        if isinstance(mh_agg, (int, float)):
            records.append(
                {
                    "series": f"serve_fleet_aggregate:{backend}",
                    "round": rnd,
                    "value": float(mh_agg),
                    "unit": "GB/s",
                    "incomplete": incomplete,
                    "file": fname,
                }
            )
        # Compressed-save throughput: the compression probe's effective
        # GB/s (logical bytes over compressed-save wall).  Its own series
        # so the --fail-on-regression gate covers compressed saves — the
        # r07→r12 frontier — not just the raw headline.  Rounds where the
        # main save ran compressed bank ratio-only probes (no wall) and
        # simply contribute no record.
        comp = aux.get("compression_probe") or {}
        eff = comp.get("effective_gbps")
        if isinstance(eff, (int, float)):
            records.append(
                {
                    "series": f"{bank}:compressed_save_gbps:{backend}",
                    "round": rnd,
                    "value": float(eff),
                    "unit": "GB/s",
                    "incomplete": incomplete,
                    "file": fname,
                }
            )
        # Churn-within-slab journal efficiency (churned bytes / appended
        # bytes, 1.0 = perfect append ∝ churn): the content-defined
        # sub-chunking acceptance number.  Its own gated series so a
        # regression back toward whole-slab re-writes (efficiency ~0.1)
        # fails the trajectory gate like any throughput loss —
        # detect_regression maps value → 1/value cost, which works for
        # any higher-is-better metric.
        slab = (aux.get("journal_probe") or {}).get("slab_mode") or {}
        churn_eff = slab.get("churn_efficiency")
        if isinstance(churn_eff, (int, float)):
            records.append(
                {
                    "series": f"{bank}:journal_slab_churn_efficiency:{backend}",
                    "round": rnd,
                    "value": float(churn_eff),
                    "unit": "churn/append",
                    "incomplete": incomplete,
                    "file": fname,
                }
            )
        # Two-tenant shared-store dedup (logical bytes / physical bytes
        # store-wide, >1 = cross-tenant sharing works): the multi-tenant
        # store's acceptance number.  Its own gated series so a change
        # that silently stops tenants from sharing backbone chunks
        # (ratio → ~1.0) fails the trajectory gate.
        store_probe = aux.get("store_probe") or {}
        store_dedup = store_probe.get("dedup_ratio")
        if isinstance(store_dedup, (int, float)):
            records.append(
                {
                    "series": f"{bank}:store_two_tenant_dedup:{backend}",
                    "round": rnd,
                    "value": float(store_dedup),
                    "unit": "logical/physical",
                    "incomplete": incomplete,
                    "file": fname,
                }
            )
        # Flight-recorder spill rate (records/s through the blackbox
        # ring's positioned pwrite): the always-on forensics budget.  Its
        # own gated series so a change that slows the spill path (a sync
        # or fsync creeping in, lock contention) fails the trajectory gate
        # — the <1% overhead claim in docs/observability.md is only true
        # while this number holds.
        bb_probe = aux.get("blackbox_probe") or {}
        bb_rate = bb_probe.get("records_per_s")
        if isinstance(bb_rate, (int, float)):
            records.append(
                {
                    "series": f"{bank}:blackbox_records_per_s:{backend}",
                    "round": rnd,
                    "value": float(bb_rate),
                    "unit": "records/s",
                    "incomplete": incomplete,
                    "file": fname,
                }
            )
        # Continuous-profiler self-overhead (% of op wall at the default
        # sampling rate: calibrated per-tick cost x ticks/second).  A
        # LOWER-is-better series — analyze_trajectory special-cases the
        # "overhead_pct" name to use the value itself as the cost and to
        # hard-fail any round above the absolute 1% budget, so a change
        # that makes the sampler tick expensive (stack walking, /proc
        # parsing, lock contention) fails the gate even if it creeps in
        # slowly enough to dodge the trailing-median check.
        prof_probe = aux.get("profiler_probe") or {}
        prof_overhead = prof_probe.get("overhead_pct")
        if isinstance(prof_overhead, (int, float)):
            records.append(
                {
                    "series": f"{bank}:profiler_overhead_pct:{backend}",
                    "round": rnd,
                    "value": float(prof_overhead),
                    "unit": "%",
                    "incomplete": incomplete,
                    "file": fname,
                }
            )
    return records


# Absolute ceiling for profiler_overhead_pct series (percent of op wall):
# the documented <1% sampling budget.
_OVERHEAD_PCT_LIMIT = 1.0


def _is_overhead_series(name: str) -> bool:
    return "overhead_pct" in name


def analyze_trajectory(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Group records into series and run trailing-median regression
    detection on each complete round, reusing history.detect_regression
    by mapping throughput to a duration-like cost (1/GBps): slower is
    bigger in both domains, so the factor semantics carry over."""
    series: Dict[str, List[Dict[str, Any]]] = {}
    for rec in sorted(records, key=lambda r: r["round"]):
        series.setdefault(rec["series"], []).append(rec)
    n_regressions = 0
    for name, recs in series.items():
        prior: List[Dict[str, Any]] = []
        for rec in recs:
            usable = (
                not rec["incomplete"]
                and isinstance(rec["value"], (int, float))
                and rec["value"] > 0
            )
            if not usable:
                rec["verdict"] = "skipped" if rec["incomplete"] else "no-value"
                continue
            # Most series are higher-is-better (GB/s, ratios): cost is
            # 1/value.  Overhead series are lower-is-better: the value IS
            # the cost, and an absolute budget applies on top of the
            # relative trailing-median check.
            if _is_overhead_series(name):
                candidate = {"action": name, "duration_s": rec["value"]}
                if rec["value"] > _OVERHEAD_PCT_LIMIT:
                    rec["verdict"] = "REGRESSION"
                    rec["regression"] = {
                        "ratio": round(
                            rec["value"] / _OVERHEAD_PCT_LIMIT, 2
                        ),
                        "factor": _OVERHEAD_PCT_LIMIT,
                        "absolute_limit_pct": _OVERHEAD_PCT_LIMIT,
                    }
                    n_regressions += 1
                    prior.append(candidate)
                    continue
            else:
                candidate = {"action": name, "duration_s": 1.0 / rec["value"]}
            regression = history.detect_regression(prior, candidate)
            if regression is not None:
                rec["verdict"] = "REGRESSION"
                rec["regression"] = regression
                n_regressions += 1
            elif len(prior) >= history.MIN_BASELINE_ENTRIES:
                rec["verdict"] = "ok"
            else:
                rec["verdict"] = "baseline"
            prior.append(candidate)
    return {
        "series": series,
        "n_rounds": len(records),
        "n_regressions": n_regressions,
    }


def render(report: Dict[str, Any]) -> str:
    lines: List[str] = []
    for name in sorted(report["series"]):
        recs = report["series"][name]
        lines.append(f"{name}")
        for rec in recs:
            value = (
                f"{rec['value']:.3f} {rec['unit'] or ''}".strip()
                if rec["value"] is not None
                else "-"
            )
            flag = rec.get("verdict", "?")
            if flag == "REGRESSION":
                reg = rec.get("regression") or {}
                flag += (
                    f" ({reg.get('ratio', '?')}x the trailing median cost, "
                    f"threshold {reg.get('factor', '?')}x)"
                )
            lines.append(
                f"  r{rec['round']:02d} {value:>14}  [{flag}]  {rec['file']}"
            )
    lines.append(
        f"{report['n_rounds']} banked round record(s), "
        f"{report['n_regressions']} regression(s)"
    )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tools/bench_trajectory.py", description=__doc__
    )
    parser.add_argument(
        "root",
        nargs="?",
        default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        help="directory holding the banked *_rNN.json files (default: repo root)",
    )
    parser.add_argument("--json", action="store_true")
    parser.add_argument(
        "--fail-on-regression",
        action="store_true",
        help="exit 1 when any complete round regresses vs its trailing median",
    )
    parser.add_argument(
        "--factor",
        type=float,
        default=None,
        help="override the regression factor (default: TPUSNAP_REGRESSION_FACTOR)",
    )
    parser.add_argument(
        "--window",
        type=int,
        default=None,
        help="override the trailing window (default: TPUSNAP_REGRESSION_WINDOW)",
    )
    args = parser.parse_args(argv)
    if not os.path.isdir(args.root):
        print(f"{args.root}: not a directory")
        return 2

    import contextlib

    ctx: Any = contextlib.ExitStack()
    with ctx:
        if args.factor is not None:
            ctx.enter_context(knobs.override_regression_factor(args.factor))
        if args.window is not None:
            ctx.enter_context(knobs.override_regression_window(args.window))
        records = collect_rounds(args.root)
        report = analyze_trajectory(records)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(render(report))
    if args.fail_on_regression and report["n_regressions"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
