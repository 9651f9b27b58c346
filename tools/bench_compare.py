"""Benchmark regression gate over the committed BENCH history.

Reference analog: `.benchrc.yaml` — the reference CI runs every perf
test, compares against the committed benchmark history and FAILS the
run when a result degrades by more than `threshold` (3x). Here the
history is the driver's `BENCH_r*.json` round files (one per PR round,
`parsed` = the bench document) plus the latest run's
`bench_details.json`; VERDICT round 5 lists "continuous benchmark
regression tracking" as missing item #3 — this tool closes it.

    python tools/bench_compare.py                 # repo history, 3x gate
    python tools/bench_compare.py --threshold 1.5 # tighter gate
    python tools/bench_compare.py --dir /path     # synthetic histories (tests)

Comparison rules:
- rounds whose document never parsed (`parsed: null` — a timed-out run)
  carry no comparable rows and are skipped WITH a printed note, exactly
  like the reference skips benchmarks with no prior history; rounds the
  bench watchdog flushed partially (`timed_out: true`, round 7) parse
  but are likewise logged-and-skipped — a truncated run's rates are not
  a trend;
- rounds that ran DEGRADED (`supervisor.degraded: true` in the bench
  document: CPU-oracle fallbacks, an open circuit breaker, or an armed
  fault-injection plan — round 7) are skipped with a printed note: a
  round served by the CPU tier measures the wrong thing, and gating on
  it would either mask a device regression or flag a phantom one;
- rate-shaped keys (`*per_sec`) regress when they DROP by more than
  threshold; time-shaped keys (`*_s`, `*_ms`, `*_seconds`) regress when
  they GROW by more than threshold; other keys (counts, fractions,
  configs) are informational only;
- REQUIRED keys (`REQUIRED_GATED_KEYS`: the per-set floor and the
  no-flags e2e rate — round-6 acceptance rows) are matched by BASE name
  across phase-prefix renames, so moving a row between phases can't
  silently drop it out of the gate; a required key present in the prior
  round but MISSING from the current one fails the run (a disappeared
  row hides regressions as effectively as a slow one);
- every round's SLO section (the bench document's `slo` verdicts from
  observability/slo.py, round 16) is compared objective-by-objective:
  the report prints each objective's prev->curr state delta, and a
  CURRENT round with a `burning` objective fails the gate WITH THE
  OBJECTIVE'S NAME — an error budget burning is a regression even when
  every raw number sits inside the 3x band. Rounds predating the SLO
  engine report `n/a` and never gate. Degraded/timed-out rounds are
  still skipped from numeric comparison, but their burn state is
  REPORTED (the skip note carries which objectives were burning when
  the round died). `--slo-only` gates exclusively on SLO verdicts;
- fewer than two parseable rounds exits 0 with a note (nothing to gate
  against), never a false red;
- each round's cumulative XLA compile seconds (the bench document's
  `compile_ledger` section) is printed as an INFORMATIONAL prev->curr
  delta, never gated: compile time varies with cache warmth, and the
  warm/cold distinction lives in the ledger itself — but a silent 10x
  compile-cost growth should at least be visible in the report.

Exit code: 0 = no regression, 1 = at least one gated key regressed (or a
required key disappeared).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

DEFAULT_THRESHOLD = 3.0
# rows the gate must never lose track of, matched by base name (the part
# after any `phase.` prefix): the unconditional per-set floor and the
# default-configuration wire-to-verdict rate
REQUIRED_GATED_KEYS = (
    # emitted by the parity-gated `floor_fused_pairing` phase (named
    # `floor_batched_fe` in ISSUE 14, `worst_case` before); base-name
    # matching carries the trend across the phase renames, same kernel +
    # shape on both sides
    "device_sets_per_sec_floor_distinct_pk_and_msg",
    "e2e_wire_to_verdict_sets_per_sec",
    # the mesh-native serving rate (round-7 tentpole): the grouped kernel
    # through the production mesh dispatcher on this host's mesh
    "sharded_grouped_sets_per_sec",
    # zero-copy wire→verdict through the mesh raw twins (ISSUE 15):
    # the facade with a mesh attached, signature bytes decompressed
    # on-device per chip — the e2e acceptance row for mesh ingest
    "e2e_mesh_raw_sets_per_sec",
    # ISSUE 18: the epoch-warm attestation-lane host-marshal rate (the
    # epoch table + H(msg) dedup win; parity-gated in its phase)
    "attestation_epoch_warm_sets_per_sec",
    # ISSUE 19: the cold-start SLO as a gated time row (direction: down —
    # a round whose serving-ready grew 3x regressed the restart story,
    # e.g. a broken AOT store silently degrading every boot to JIT)
    "serving_ready_seconds",
    # ISSUE 20: the two-level fleet serving rate (the grouped kernel
    # through the emulated 2-host (dcn, ici) mesh; absent history skips
    # the gate, so pre-fleet rounds stay green)
    "fleet_sets_per_sec",
)
REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)


def _numeric_rows(doc: dict) -> dict[str, float]:
    """Flatten one bench document into {key: value} comparable rows:
    the headline metric plus every numeric per-phase row (the
    bench_emit.BenchEmitter layout) or flat legacy-format key."""
    rows: dict[str, float] = {}
    if not isinstance(doc, dict):
        return rows
    metric = doc.get("metric")
    if metric and isinstance(doc.get("value"), (int, float)):
        rows[str(metric)] = float(doc["value"])
    phases = doc.get("phases")
    if isinstance(phases, dict):
        for phase, rec in phases.items():
            if not isinstance(rec, dict) or rec.get("status") not in (None, "ok"):
                continue  # timed-out/killed phases are not comparable
            for key, value in (rec.get("rows") or {}).items():
                if isinstance(value, (int, float)) and not isinstance(value, bool):
                    rows[f"{phase}.{key}"] = float(value)
    else:
        # legacy flat details document (rounds <= 5)
        for key, value in doc.items():
            if key in ("metric", "value", "vs_baseline", "partial"):
                continue
            if isinstance(value, (int, float)) and not isinstance(value, bool):
                rows[str(key)] = float(value)
    return rows


def _compile_seconds(doc) -> float | None:
    """Cumulative XLA compile seconds from the document's compile-ledger
    section (observability/compile_ledger.py), or None when the round
    predates the ledger."""
    if not isinstance(doc, dict):
        return None
    section = doc.get("compile_ledger")
    if not isinstance(section, dict):
        return None
    value = section.get("cumulative_seconds")
    if isinstance(value, (int, float)) and not isinstance(value, bool):
        return float(value)
    return None


def _slo_state(doc) -> dict | None:
    """{objective: state} + the burning list from the bench document's
    `slo` section (observability/slo.py), or None for rounds predating
    the SLO engine."""
    section = doc.get("slo") if isinstance(doc, dict) else None
    if not isinstance(section, dict):
        return None
    objectives = {
        o["name"]: o.get("state", "?")
        for o in section.get("objectives", ())
        if isinstance(o, dict) and o.get("name")
    }
    if not objectives:
        return None
    return {
        "objectives": objectives,
        "burning": sorted(k for k, v in objectives.items() if v == "burning"),
    }


def _print_burn_state(n: int, slo: dict | None) -> None:
    """One-line burn-state report for a round skipped from numeric
    comparison (degraded/timed-out): the skip must still say what the
    objectives looked like when the round died."""
    if slo is None:
        print(f"bench_compare: r{n:02d} burn state — n/a (round predates "
              "the SLO engine)")
    elif slo["burning"]:
        print(
            f"bench_compare: r{n:02d} burn state — BURNING: "
            f"{', '.join(slo['burning'])}"
        )
    else:
        print(
            f"bench_compare: r{n:02d} burn state — all "
            f"{len(slo['objectives'])} objectives ok"
        )


def _is_degraded(doc) -> bool:
    """A bench document that ran with CPU fallbacks / open breaker /
    armed faults labels itself via the emitter's `supervisor` section."""
    sup = doc.get("supervisor") if isinstance(doc, dict) else None
    return bool(isinstance(sup, dict) and sup.get("degraded"))


def load_history(root_dir: str, details_path: str | None = None) -> list[dict]:
    """[{n, rows}] for every round whose bench document parsed AND ran
    non-degraded, ascending by round number. `details_path`
    (bench_details.json) augments the LATEST round with its full
    per-phase row set (unless that document is itself degraded)."""
    rounds = []
    for path in glob.glob(os.path.join(root_dir, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            rec = json.load(open(path))
        except (OSError, ValueError) as e:
            print(
                f"bench_compare: skipping r{int(m.group(1)):02d} — "
                f"unreadable round file ({e})"
            )
            continue
        if not isinstance(rec, dict):
            # a `null` / truncated / list-shaped round file: carries no
            # rows, but must be a visible skip and never a traceback
            print(
                f"bench_compare: skipping r{int(m.group(1)):02d} — round "
                f"file is not a JSON object ({type(rec).__name__})"
            )
            continue
        parsed = rec.get("parsed") or {}
        if not isinstance(parsed, dict):
            print(
                f"bench_compare: skipping r{int(m.group(1)):02d} — "
                f"`parsed` is not a JSON object ({type(parsed).__name__})"
            )
            continue
        if not parsed:
            # `parsed: null` — the harness died before emitting; the
            # round carries no comparable rows but its absence from the
            # gate must be visible, not silent
            print(
                f"bench_compare: skipping r{int(m.group(1)):02d} — bench "
                "document never parsed (parsed: null; harness killed "
                "before emission)"
            )
            continue
        if _is_degraded(parsed):
            print(
                f"bench_compare: skipping r{int(m.group(1)):02d} — ran "
                "DEGRADED (CPU fallback / open breaker / faults armed); "
                "not comparable to device-path rounds"
            )
            _print_burn_state(int(m.group(1)), _slo_state(parsed))
            continue
        if parsed.get("timed_out"):
            # the watchdog/SIGTERM flushed a PARTIAL document before the
            # driver's kill: parseable, but its rates stop mid-run — log
            # and skip instead of gating a truncated round
            print(
                f"bench_compare: skipping r{int(m.group(1)):02d} — timed "
                "out mid-run (partial watchdog flush); rates not "
                "comparable to completed rounds"
            )
            _print_burn_state(int(m.group(1)), _slo_state(parsed))
            continue
        rows = _numeric_rows(parsed)
        if rows:
            rounds.append({
                "n": int(m.group(1)),
                "rows": rows,
                "compile_s": _compile_seconds(parsed),
                "slo": _slo_state(parsed),
            })
    rounds.sort(key=lambda r: r["n"])
    if rounds and details_path and os.path.exists(details_path):
        try:
            detail_doc = json.load(open(details_path))
            if _is_degraded(detail_doc) or (
                isinstance(detail_doc, dict) and detail_doc.get("timed_out")
            ):
                detail_rows = {}
            else:
                detail_rows = _numeric_rows(detail_doc)
        except (OSError, ValueError):
            detail_rows = {}
        # details belong to the newest run: augment without overriding
        # the round file's own headline
        for key, value in detail_rows.items():
            rounds[-1]["rows"].setdefault(key, value)
        if rounds[-1].get("compile_s") is None and detail_rows:
            rounds[-1]["compile_s"] = _compile_seconds(detail_doc)
        if rounds[-1].get("slo") is None and detail_rows:
            rounds[-1]["slo"] = _slo_state(detail_doc)
    return rounds


def compare_slo(prev: dict, curr: dict) -> tuple[list, list]:
    """(report_rows, regressions) for the SLO verdicts: every objective
    seen in either round gets a prev->curr state line, and an objective
    BURNING in the current round is a named regression — the whole point
    of the engine is that a burnt budget fails the gate by name."""
    prev_slo = prev.get("slo")
    curr_slo = curr.get("slo")
    report, regressions = [], []
    prev_obj = prev_slo["objectives"] if prev_slo else {}
    curr_obj = curr_slo["objectives"] if curr_slo else {}
    for name in sorted(set(prev_obj) | set(curr_obj)):
        p = prev_obj.get(name, "n/a")
        c = curr_obj.get(name, "n/a")
        burning_now = c == "burning"
        report.append((name, p, c, burning_now))
        if burning_now:
            regressions.append(f"slo:{name} (error budget burning)")
    return report, regressions


def _direction(key: str) -> str | None:
    """'up' = higher is better (rates), 'down' = lower is better
    (latencies), None = not gated."""
    base = key.rsplit(".", 1)[-1]
    if base.endswith("per_sec"):
        return "up"
    if base.endswith(("_s", "_ms", "_seconds")):
        return "down"
    if base == "fleet_overlap_fraction":
        # ISSUE 20: retained-throughput fraction of the two-level mesh
        # vs the flat mesh — a drop means the DCN collectives stopped
        # overlapping (e.g. a hierarchy regression re-crossing DCN per
        # bit-plane), which a raw rate row could hide behind faster chips
        return "up"
    return None


def _find_by_base(rows: dict, base: str):
    """(key, value) whose base name (after any `phase.` prefix) matches,
    or None. Exact-name match wins over a prefixed one."""
    if base in rows:
        return base, rows[base]
    for key, value in rows.items():
        if key.rsplit(".", 1)[-1] == base:
            return key, value
    return None


def compare(prev: dict, curr: dict, threshold: float) -> tuple[list, list]:
    """(report_rows, regressions) between two rounds' row dicts.

    Beyond the exact-key intersection, every REQUIRED_GATED_KEYS entry is
    resolved by base name on both sides so phase renames can't drop it;
    required keys present before but absent now count as regressions."""
    report, regressions = [], []
    compared = set()
    for key in sorted(set(prev["rows"]) & set(curr["rows"])):
        direction = _direction(key)
        if direction is None:
            continue
        p, c = prev["rows"][key], curr["rows"][key]
        if p <= 0 or c <= 0:
            continue  # zero/negative rows carry no trend information
        ratio = (p / c) if direction == "up" else (c / p)
        regressed = ratio > threshold
        report.append((key, direction, p, c, ratio, regressed))
        compared.add(key.rsplit(".", 1)[-1])
        if regressed:
            regressions.append(key)
    for base in REQUIRED_GATED_KEYS:
        if base in compared:
            continue
        prev_hit = _find_by_base(prev["rows"], base)
        curr_hit = _find_by_base(curr["rows"], base)
        if prev_hit is None:
            continue  # no history for this row yet — nothing to gate
        if curr_hit is None:
            # the row vanished: treat as a failed gate, not a silent skip
            report.append((base, "up", prev_hit[1], 0.0, float("inf"), True))
            regressions.append(f"{base} (missing from current round)")
            continue
        direction = _direction(base) or "up"
        p, c = prev_hit[1], curr_hit[1]
        if p <= 0 or c <= 0:
            continue
        ratio = (p / c) if direction == "up" else (c / p)
        regressed = ratio > threshold
        report.append((base, direction, p, c, ratio, regressed))
        if regressed:
            regressions.append(base)
    # ISSUE 20: fleet parity is a hard acceptance bit, not a trend — a
    # current round whose fleet_dryrun phase emitted fleet_parity_ok=0
    # diverged two-level verdicts from the flat mesh and fails outright,
    # whatever the rate rows say
    parity = _find_by_base(curr["rows"], "fleet_parity_ok")
    if parity is not None and parity[1] < 1:
        regressions.append(
            "fleet_parity_ok (two-level verdicts diverged from flat mesh)"
        )
    return report, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dir", default=REPO_ROOT,
                    help="directory holding BENCH_r*.json round files")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="regression factor that fails the gate (ref: 3x)")
    ap.add_argument("--details", default=None,
                    help="bench_details.json for the latest round "
                         "(default: <dir>/bench_details.json)")
    ap.add_argument("--slo-only", action="store_true",
                    help="gate exclusively on SLO verdicts (skip the "
                         "numeric threshold comparison)")
    args = ap.parse_args(argv)

    details = args.details or os.path.join(args.dir, "bench_details.json")
    history = load_history(args.dir, details_path=details)
    if len(history) < 2:
        if not history:
            print(
                f"bench_compare: no parseable bench history in {args.dir} "
                "— nothing to gate against (a fresh checkout or an "
                "all-degraded history is not a failure)"
            )
        else:
            print(
                f"bench_compare: 1 parseable round in {args.dir} — "
                "nothing to gate against"
            )
        return 0
    prev, curr = history[-2], history[-1]
    slo_report, slo_regressions = compare_slo(prev, curr)
    regressions = []
    if args.slo_only:
        print(
            f"bench_compare: r{prev['n']:02d} -> r{curr['n']:02d} "
            "(--slo-only: numeric thresholds skipped)"
        )
    else:
        report, regressions = compare(prev, curr, args.threshold)
        print(
            f"bench_compare: r{prev['n']:02d} -> r{curr['n']:02d} "
            f"({len(report)} gated keys, threshold {args.threshold}x)"
        )
        for key, direction, p, c, ratio, regressed in report:
            tag = "REGRESSION" if regressed else "ok"
            arrow = "^" if direction == "up" else "v"
            print(
                f"  {tag:>10}  {key} [{arrow}]  {p:.2f} -> {c:.2f}  "
                f"(worse x{ratio:.2f})" if ratio > 1.0 else
                f"  {tag:>10}  {key} [{arrow}]  {p:.2f} -> {c:.2f}  "
                f"(better x{1 / ratio:.2f})"
            )
        pc, cc = prev.get("compile_s"), curr.get("compile_s")
        if pc is not None or cc is not None:
            def _fmt(v):
                return f"{v:.1f}s" if v is not None else "n/a"

            print(
                f"  info        cumulative compile seconds {_fmt(pc)} -> "
                f"{_fmt(cc)} (informational; not gated — varies with cache "
                "warmth, see compile_ledger)"
            )
    if slo_report:
        print(f"  slo verdicts r{prev['n']:02d} -> r{curr['n']:02d}:")
        for name, p, c, burning_now in slo_report:
            tag = "BURNING" if burning_now else "ok"
            print(f"  {tag:>10}  slo:{name}  {p} -> {c}")
    else:
        print(
            "  info        no SLO verdicts in either round (rounds predate "
            "the SLO engine; not gated)"
        )
    failed = False
    if regressions:
        print(
            f"FAIL: {len(regressions)} key(s) regressed more than "
            f"{args.threshold}x: {', '.join(regressions)}"
        )
        failed = True
    if slo_regressions:
        print(
            f"FAIL: {len(slo_regressions)} SLO objective(s) burning their "
            f"error budget: {', '.join(slo_regressions)}"
        )
        failed = True
    if failed:
        return 1
    if args.slo_only:
        print("OK: no SLO objective is burning its error budget")
    else:
        print("OK: no gated key regressed past the threshold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
