#!/usr/bin/env python
"""Benchmark regression gate: compare a fresh BENCH_*.json against a
named baseline and fail loudly on a throughput regression.

``bench.py`` prints one JSON line per run ({"metric", "value", "unit",
...}); the driver archives them as ``BENCH_rNN.json`` (either the bare
result object or the driver envelope whose ``tail``/``parsed`` fields
hold it).  This tool makes those files actionable:

    python tools/bench_regress.py --baseline /tmp/bench_base.json \
        --candidate /tmp/bench_new.json --threshold 5

exits 0 when the candidate's ``value`` is within ``--threshold`` percent
below the baseline (higher is always better here — both bench modes
report rates), 1 on a regression, 2 on unreadable/mismatched inputs.
The one-line JSON verdict on stdout carries both values and the delta so
a CI log shows the numbers, not just the exit code.

``--warmup-threshold <pct>`` additionally gates the WARMUP tax (the XLA
compile seconds before the timed windows): the candidate's COLD warmup
may exceed the baseline's by at most that many percent.  Since round 7
bench.py splits warmup into ``warmup_cold_s`` (first boot, compiles) and
``warmup_warm_s`` (second booster, compile caches hot); the gate reads
``warmup_cold_s`` and falls back to ``warmup_s`` (always a cold number,
first-class key since round 6) so pre-r07 baselines compare like with
like; for even older baselines the value is recovered from the
``warmup_s=...`` field of the driver envelope's tail comment.  The warm
number rides along in the verdict uninspected.  Lower warmup is always fine — the gate is
one-sided, like the throughput gate.  Mind that warmup variance dwarfs
throughput variance (34-321 s across four early rounds for identical
code: cold compiles against persistent-cache hits; PERF.md, "Carried
over"); gate wide, or pin the environment first.  Intended CI shape
once a TPU runner exists (docs/OBSERVABILITY.md §Benchmark regression
gate):

    python bench.py > /tmp/bench_new.json
    python tools/bench_regress.py --baseline /tmp/bench_base.json \
        --candidate /tmp/bench_new.json --threshold 10

Mind the variance recorded in PERF.md ("Carried over"): an earlier
installation measured 5.9-7.5 it/s for identical code across a day, so gate with a
threshold wider than the observed window spread (the JSON's ``spread``
tail comment) or on a quiet runner.

Round 8's ``bench.py --mode predict --concurrency N`` adds ``fleet`` /
``concurrency`` keys (per-replica-count rows/sec + shed rate); they pass
through into the verdict informationally on whichever side carries them
and are never required — old baselines keep comparing.  Round 9 adds an
``availability`` block the same way (``serve_retries_total`` /
``serve_ejections_total`` / ``serve_deadline_expired_total`` deltas over
the bench run): informational, never gated, never required.

``--program-threshold <pct>`` gates PER-PROGRAM device seconds from the
``profile`` block (PR 16, obs/devprof.py): for every XLA program present
on both sides with a positive baseline ``device_seconds_est``, the
candidate may exceed the baseline by at most that many percent — the
instrument ROADMAP item 1's fused-vs-ordered A/B needs ("the end-to-end
rate held, but grow_tree got 40% slower" fails loudly instead of hiding
inside the aggregate).  Both bench runs must profile (run with
LIGHTGBM_TPU_DEVPROF=sample:N; sampling correction makes estimates
comparable across different N).  When either side carries no profiled
programs — every pre-r16 baseline — the per-program gate records a note
and passes: old baselines keep comparing, exactly like the other
informational blocks, and the ``profile``/``device`` summaries ride
along per side when present.

``--latency-threshold <pct>`` gates PER-BATCH p99 latency from the
``latency_sweep`` block (PR 20, the fused Pallas forest-walk kernel):
``bench.py --mode predict`` times single calls at batch 1/16/64/256 per
serving strategy and records p50/p99 milliseconds.  For every
(strategy, batch) point present on both sides the candidate's p99 may
exceed the baseline's by at most that many percent — the end-to-end
rows/sec gate averages tail latency away, and tail latency is exactly
what the fused walk exists to shrink.  When either side lacks the block
(pre-r20 baselines, --mode train runs) the gate records a note and
passes, like the per-program gate.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Any, Dict, Optional


def extract_result(path: str) -> Dict[str, Any]:
    """Load a bench result from either a bare bench.py JSON line or a
    driver envelope (``parsed`` field, or the last JSON object line of a
    ``tail`` transcript).  ``warmup_s`` is folded in from the tail's
    ``warmup_s=...`` stderr comment when the result object itself does
    not carry it (pre-round-6 BENCH files)."""
    with open(path) as fh:
        text = fh.read()
    obj = json.loads(text)
    if "value" in obj and "metric" in obj:
        return obj
    result: Optional[Dict[str, Any]] = None
    if isinstance(obj.get("parsed"), dict) and "value" in obj["parsed"]:
        result = dict(obj["parsed"])
    tail = str(obj.get("tail", ""))
    if result is None:
        for line in tail.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    cand = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "value" in cand and "metric" in cand:
                    result = cand
    if result is None:
        raise ValueError(f"{path}: no bench result object found")
    if "warmup_s" not in result:
        m = re.search(r"\bwarmup_s=([0-9]+(?:\.[0-9]+)?)", tail)
        if m:
            result["warmup_s"] = float(m.group(1))
    return result


def compare(baseline: Dict[str, Any], candidate: Dict[str, Any],
            threshold_pct: float,
            warmup_threshold_pct: Optional[float] = None,
            program_threshold_pct: Optional[float] = None,
            latency_threshold_pct: Optional[float] = None) -> Dict[str, Any]:
    """Verdict dict; ``ok`` is False when the candidate regressed more
    than ``threshold_pct`` percent below the baseline value, (with a
    warmup threshold) when its warmup exceeds the baseline's by more
    than ``warmup_threshold_pct`` percent, (with a program threshold)
    when any program's estimated device seconds grew by more than
    ``program_threshold_pct`` percent — skipped with a note when either
    side carries no profiled programs — or (with a latency threshold)
    when any ``latency_sweep`` p99 grew by more than
    ``latency_threshold_pct`` percent at any (strategy, batch) point
    present on both sides — likewise skipped with a note when either
    side lacks the block."""
    if baseline.get("metric") != candidate.get("metric"):
        raise ValueError(
            f"metric mismatch: baseline {baseline.get('metric')!r} vs "
            f"candidate {candidate.get('metric')!r} — comparing different "
            f"workloads is not a regression check")
    base = float(baseline["value"])
    cand = float(candidate["value"])
    if base <= 0:
        raise ValueError(f"baseline value {base} is not a positive rate")
    delta_pct = (cand - base) / base * 100.0
    verdict = {
        "metric": baseline.get("metric"),
        "unit": baseline.get("unit"),
        "baseline": base,
        "candidate": cand,
        "delta_pct": round(delta_pct, 3),
        "threshold_pct": float(threshold_pct),
        "ok": delta_pct >= -float(threshold_pct),
    }
    if warmup_threshold_pct is not None:
        # round 7 split warmup into warmup_cold_s (first-boot compile
        # tax) and warmup_warm_s (steady-state, compile caches hot); the
        # gate compares COLD with cold — pre-r07 baselines carry only
        # warmup_s, which was always a cold measurement, so falling back
        # to it keeps the comparison like-with-like.
        wb = baseline.get("warmup_cold_s", baseline.get("warmup_s"))
        wc = candidate.get("warmup_cold_s", candidate.get("warmup_s"))
        if wb is None or wc is None:
            # a warmup gate over sides that never measured warmup would
            # silently pass forever — that is an input error, not a pass
            missing = [side for side, w in (("baseline", wb),
                                            ("candidate", wc)) if w is None]
            raise ValueError(
                f"--warmup-threshold given but {' and '.join(missing)} "
                f"carr{'y' if len(missing) > 1 else 'ies'} no warmup_s "
                f"(neither as a JSON key nor in the tail comment)")
        wb, wc = float(wb), float(wc)
        wdelta = ((wc - wb) / wb * 100.0) if wb > 0 else \
            (0.0 if wc <= 0 else float("inf"))
        verdict.update({
            "warmup_baseline_s": wb,
            "warmup_candidate_s": wc,
            "warmup_delta_pct": round(wdelta, 3) if wdelta != float("inf")
            else None,
            "warmup_threshold_pct": float(warmup_threshold_pct),
            "warmup_ok": wdelta <= float(warmup_threshold_pct),
        })
        # informational: the warm-restart warmup, when both sides have it
        # (r07+); not gated — its whole point is to be near zero, and the
        # cold gate already guards the compile tax
        for side, obj in (("baseline", baseline), ("candidate", candidate)):
            if obj.get("warmup_warm_s") is not None:
                verdict[f"warmup_warm_{side}_s"] = float(obj["warmup_warm_s"])
        verdict["ok"] = verdict["ok"] and verdict["warmup_ok"]
    if program_threshold_pct is not None:
        bp = (baseline.get("profile") or {}).get("programs") or {}
        cp = (candidate.get("profile") or {}).get("programs") or {}
        deltas: Dict[str, Any] = {}
        progs_ok = True
        for prog in sorted(set(bp) & set(cp)):
            b = (bp[prog] or {}).get("device_seconds_est")
            c = (cp[prog] or {}).get("device_seconds_est")
            if b is None or c is None or float(b) <= 0:
                continue
            d = (float(c) - float(b)) / float(b) * 100.0
            ok = d <= float(program_threshold_pct)
            deltas[prog] = {"baseline_s": round(float(b), 6),
                            "candidate_s": round(float(c), 6),
                            "delta_pct": round(d, 3), "ok": ok}
            progs_ok = progs_ok and ok
        verdict["program_threshold_pct"] = float(program_threshold_pct)
        verdict["programs_delta"] = deltas
        if not bp or not cp:
            # pre-r16 BENCH files (or runs with devprof off) carry no
            # profiled programs — the gate must not fail them, or every
            # historical baseline stops comparing; record WHY it passed
            missing = [s for s, p in (("baseline", bp),
                                      ("candidate", cp)) if not p]
            verdict["programs_ok"] = True
            verdict["programs_note"] = (
                f"profile programs missing on {' and '.join(missing)} — "
                f"per-program gate skipped (run bench with "
                f"LIGHTGBM_TPU_DEVPROF to gate)")
        else:
            verdict["programs_ok"] = progs_ok
            verdict["ok"] = verdict["ok"] and progs_ok
    if latency_threshold_pct is not None:
        # PR 20: bench.py --mode predict emits a ``latency_sweep`` block
        # (per serving strategy, per batch size: p50_ms/p99_ms over
        # single-call dispatches).  The gate is on p99 — tail latency is
        # what the fused walk kernel exists to shrink, and an end-to-end
        # rows/sec gate averages it away.  Compared per (strategy, batch)
        # point present on BOTH sides; one-sided, like every other gate.
        bl = (baseline.get("latency_sweep") or {}).get("strategies") or {}
        cl = (candidate.get("latency_sweep") or {}).get("strategies") or {}
        ldeltas: Dict[str, Any] = {}
        lat_ok = True
        for strat in sorted(set(bl) & set(cl)):
            bpts, cpts = bl[strat] or {}, cl[strat] or {}
            for batch in sorted(set(bpts) & set(cpts), key=int):
                b = (bpts[batch] or {}).get("p99_ms")
                c = (cpts[batch] or {}).get("p99_ms")
                if b is None or c is None or float(b) <= 0:
                    continue
                d = (float(c) - float(b)) / float(b) * 100.0
                ok = d <= float(latency_threshold_pct)
                ldeltas[f"{strat}/{batch}"] = {
                    "baseline_p99_ms": round(float(b), 4),
                    "candidate_p99_ms": round(float(c), 4),
                    "delta_pct": round(d, 3), "ok": ok}
                lat_ok = lat_ok and ok
        verdict["latency_threshold_pct"] = float(latency_threshold_pct)
        verdict["latency_delta"] = ldeltas
        if not bl or not cl:
            # pre-r20 BENCH files (or --mode train runs) carry no latency
            # sweep — the gate must not fail them, or every historical
            # baseline stops comparing; record WHY it passed
            missing = [s for s, p in (("baseline", bl),
                                      ("candidate", cl)) if not p]
            verdict["latency_ok"] = True
            verdict["latency_note"] = (
                f"latency_sweep missing on {' and '.join(missing)} — "
                f"latency gate skipped (run bench.py --mode predict to "
                f"gate)")
        else:
            verdict["latency_ok"] = lat_ok
            verdict["ok"] = verdict["ok"] and lat_ok
    # informational: the serving-fleet scaling curve (round 8's
    # ``bench.py --mode predict --concurrency N`` adds ``fleet`` /
    # ``concurrency`` keys) rides along in the verdict per side when
    # present — not gated (replica counts vary per box), never an error
    # when absent (pre-r08 baselines)
    for side, obj in (("baseline", baseline), ("candidate", candidate)):
        fleet = obj.get("fleet")
        if isinstance(fleet, dict) and fleet:
            verdict[f"fleet_{side}_rows_per_sec"] = {
                r: blk.get("rows_per_sec")
                for r, blk in sorted(fleet.items(),
                                     key=lambda kv: int(kv[0]))
                if isinstance(blk, dict)}
            shed = {r: blk.get("shed_rate") for r, blk in fleet.items()
                    if isinstance(blk, dict) and blk.get("shed_rate")}
            if shed:
                verdict[f"fleet_{side}_shed_rate"] = shed
        # round 9: serving availability counters (hedged retries,
        # replica ejections, deadline sheds) ride along informationally —
        # a chaos-y bench run should show its fault bill in the verdict,
        # but replica health is environment-dependent, so never gated
        avail = obj.get("availability")
        if isinstance(avail, dict) and avail:
            verdict[f"availability_{side}"] = avail
        # PR 13: a train run that QUARANTINED bad rows says so in the
        # verdict — a throughput number over a partially-skipped
        # dataset carries its asterisk, but dirt volume is data-
        # dependent, so never gated
        bad = obj.get("bad_rows")
        if isinstance(bad, dict) and bad:
            verdict[f"bad_rows_{side}"] = bad
        # PR 15: resource bill (docs/FAULT_TOLERANCE.md §Resource
        # exhaustion) — estimated vs measured peak bytes, degrade-ladder
        # steps taken, sink write errors.  Informational: degrade steps
        # are budget-dependent, never gated, never required (old
        # baselines keep comparing)
        res = obj.get("resource")
        if isinstance(res, dict) and res:
            verdict[f"resource_{side}"] = res
        # PR 14: wide-sparse training bill (docs/SPARSE.md) — EFB bundle
        # shrinkage, screening's active-feature trajectory, and the run's
        # AUC ride along informationally so an A/B ctrlike comparison
        # (bundling/screening on vs off) shows its accuracy asterisk;
        # never gated, never required (old baselines keep comparing)
        # PR 18: piece-wise linear trees bill (docs/LINEAR_TREES.md) —
        # trees-to-target vs the constant run, per-round fit seconds,
        # leaf-fit fallback rate.  Informational: accuracy trade-offs are
        # workload-dependent, never gated, never required
        # PR 19: drift observatory bill (docs/OBSERVABILITY.md §Drift) —
        # window PSI summary + collector compute seconds from --mode
        # predict.  Informational: old baselines have no drift block
        for key in ("efb", "screening", "linear", "drift"):
            blk = obj.get(key)
            if isinstance(blk, dict) and blk:
                verdict[f"{key}_{side}"] = blk
        if obj.get("auc") is not None:
            verdict[f"auc_{side}"] = float(obj["auc"])
        # PR 16: device-time attribution summary + hardware identity
        # (bench.py `profile`/`device` blocks) — informational per side;
        # the gated view lives under programs_delta when
        # --program-threshold is given
        prof = obj.get("profile")
        if isinstance(prof, dict) and prof:
            verdict[f"profile_{side}"] = {
                "mode": prof.get("mode"),
                "device_seconds_est_total":
                    prof.get("device_seconds_est_total"),
                "rounds": prof.get("rounds"),
            }
        dev = obj.get("device")
        if isinstance(dev, dict) and dev:
            verdict[f"device_{side}"] = {
                "platform": dev.get("platform"),
                "device_kind": dev.get("device_kind"),
                "jax_version": dev.get("jax_version"),
            }
    return verdict


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail on >threshold%% bench throughput regression")
    ap.add_argument("--baseline", required=True,
                    help="baseline BENCH_*.json (bare result or driver "
                         "envelope)")
    ap.add_argument("--candidate", required=True,
                    help="fresh bench.py output JSON to check")
    ap.add_argument("--threshold", type=float, default=5.0,
                    help="allowed regression in percent (default 5)")
    ap.add_argument("--warmup-threshold", type=float, default=None,
                    help="also gate warmup_s: allowed warmup INCREASE in "
                         "percent over the baseline (off by default)")
    ap.add_argument("--program-threshold", type=float, default=None,
                    help="also gate per-program device seconds from the "
                         "profile block: allowed INCREASE in percent per "
                         "XLA program (off by default; skipped with a "
                         "note when either side has no profile data)")
    ap.add_argument("--latency-threshold", type=float, default=None,
                    help="also gate per-batch p99 latency from the "
                         "latency_sweep block: allowed INCREASE in "
                         "percent per (strategy, batch) point (off by "
                         "default; skipped with a note when either side "
                         "has no latency sweep)")
    args = ap.parse_args(argv)
    try:
        verdict = compare(extract_result(args.baseline),
                          extract_result(args.candidate), args.threshold,
                          warmup_threshold_pct=args.warmup_threshold,
                          program_threshold_pct=args.program_threshold,
                          latency_threshold_pct=args.latency_threshold)
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as exc:
        print(f"bench_regress: {exc}", file=sys.stderr)
        return 2
    print(json.dumps(verdict))
    if not verdict["ok"]:
        if not verdict.get("warmup_ok", True):
            print(f"bench_regress: WARMUP REGRESSION "
                  f"{verdict['warmup_candidate_s']:g}s vs baseline "
                  f"{verdict['warmup_baseline_s']:g}s "
                  f"(threshold +{args.warmup_threshold:g}%)",
                  file=sys.stderr)
        if not verdict.get("programs_ok", True):
            worst = max(
                (d for d in verdict.get("programs_delta", {}).items()
                 if not d[1]["ok"]),
                key=lambda d: d[1]["delta_pct"])
            print(f"bench_regress: PROGRAM REGRESSION {worst[0]} "
                  f"{worst[1]['delta_pct']:+.2f}% device time "
                  f"(threshold +{args.program_threshold:g}%)",
                  file=sys.stderr)
        if not verdict.get("latency_ok", True):
            worst = max(
                (d for d in verdict.get("latency_delta", {}).items()
                 if not d[1]["ok"]),
                key=lambda d: d[1]["delta_pct"])
            print(f"bench_regress: LATENCY REGRESSION {worst[0]} p99 "
                  f"{worst[1]['delta_pct']:+.2f}% "
                  f"(threshold +{args.latency_threshold:g}%)",
                  file=sys.stderr)
        if verdict["delta_pct"] < -args.threshold:
            print(f"bench_regress: REGRESSION {verdict['delta_pct']:+.2f}% "
                  f"(threshold -{args.threshold:g}%) on {verdict['metric']}",
                  file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
