"""tools/bench_compare.py: the CI benchmark regression gate (ISSUE 2;
reference analog `.benchrc.yaml` 3x threshold) exercised on synthetic
BENCH histories and on the repo's committed history."""

import importlib.util
import json
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    path = os.path.join(REPO_ROOT, "tools", "bench_compare.py")
    spec = importlib.util.spec_from_file_location("bench_compare", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _round(tmp_path, n, value, phases=None, parsed=True, extra=None):
    doc = {"n": n, "rc": 0 if parsed else 124, "parsed": None}
    if parsed:
        doc["parsed"] = {
            "metric": "bls_signature_sets_verified_per_sec",
            "value": value,
            "unit": "sets/s",
        }
        if phases:
            doc["parsed"]["phases"] = phases
        if extra:
            doc["parsed"].update(extra)
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))


def test_exits_nonzero_on_3x_regression(tmp_path, capsys):
    mod = _load()
    _round(tmp_path, 1, 9000.0)
    _round(tmp_path, 2, 2000.0)  # 4.5x drop
    assert mod.main(["--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION" in out and "bls_signature_sets_verified_per_sec" in out


def test_exits_zero_on_improvement_and_mild_drop(tmp_path, capsys):
    mod = _load()
    _round(tmp_path, 1, 8000.0)
    _round(tmp_path, 2, 9000.0)  # improvement
    assert mod.main(["--dir", str(tmp_path)]) == 0
    _round(tmp_path, 3, 4000.0)  # 2.25x drop: inside the 3x budget
    assert mod.main(["--dir", str(tmp_path)]) == 0
    capsys.readouterr()
    # a tighter gate catches the same drop
    assert mod.main(["--dir", str(tmp_path), "--threshold", "1.5"]) == 1
    capsys.readouterr()


def test_unparseable_rounds_are_skipped(tmp_path, capsys):
    """A timed-out round (parsed: null — the BENCH_r05 mode) carries no
    rows; the gate compares the last two PARSEABLE rounds instead of
    false-failing."""
    mod = _load()
    _round(tmp_path, 1, 8000.0)
    _round(tmp_path, 2, 9000.0)
    _round(tmp_path, 3, 0.0, parsed=False)
    assert mod.main(["--dir", str(tmp_path)]) == 0
    assert "r01 -> r02" in capsys.readouterr().out


def test_phase_rows_and_time_keys_compare(tmp_path, capsys):
    """New-format documents (bench_emit phases) flatten into gated rows;
    latency keys regress on GROWTH, and timed-out phases are skipped."""
    mod = _load()
    _round(tmp_path, 1, 9000.0, phases={
        "e2e": {"status": "ok", "rows": {"e2e_wire_to_verdict_sets_per_sec": 2000.0}},
        "hasher": {"status": "ok", "rows": {"hasher_1m_one_change_ms": 12.0}},
    })
    _round(tmp_path, 2, 9000.0, phases={
        "e2e": {"status": "ok", "rows": {"e2e_wire_to_verdict_sets_per_sec": 1900.0}},
        "hasher": {"status": "ok", "rows": {"hasher_1m_one_change_ms": 50.0}},
    })
    assert mod.main(["--dir", str(tmp_path)]) == 1  # 12 -> 50 ms: >3x slower
    assert "hasher.hasher_1m_one_change_ms" in capsys.readouterr().out
    # a timed-out NON-required phase in the latest round drops out of the
    # comparison (the REQUIRED e2e row must still be present — it's gated
    # by name; see test_required_key_missing_fails)
    _round(tmp_path, 3, 9000.0, phases={
        "e2e": {"status": "ok", "rows": {"e2e_wire_to_verdict_sets_per_sec": 1850.0}},
        "hasher": {"status": "timeout", "rows": {}},
    })
    assert mod.main(["--dir", str(tmp_path)]) == 0
    capsys.readouterr()


def test_insufficient_history_is_not_a_failure(tmp_path, capsys):
    mod = _load()
    assert mod.main(["--dir", str(tmp_path)]) == 0
    _round(tmp_path, 1, 9000.0)
    assert mod.main(["--dir", str(tmp_path)]) == 0
    assert "nothing to gate" in capsys.readouterr().out


def test_committed_bench_history_passes():
    """The acceptance gate: the repo's own BENCH_r*.json history must be
    green. Chip records now live in PERF_LEDGER.jsonl, so the committed
    history is empty and the gate has nothing to hold against."""
    mod = _load()
    assert mod.main(["--dir", REPO_ROOT]) == 0


def test_details_file_augments_latest_round(tmp_path, capsys):
    mod = _load()
    # legacy flat rows (rounds <= 5 style) in the prior round
    _round(tmp_path, 1, 9000.0,
           extra={"e2e_wire_to_verdict_sets_per_sec": 2000.0})
    _round(tmp_path, 2, 9000.0)
    details = tmp_path / "bench_details.json"
    # legacy flat details format: rows merge into the latest round
    details.write_text(json.dumps({
        "metric": "bls_signature_sets_verified_per_sec",
        "value": 9000.0,
        "e2e_wire_to_verdict_sets_per_sec": 500.0,  # 4x drop vs r01
    }))
    assert mod.main(["--dir", str(tmp_path), "--details", str(details)]) == 1
    assert "e2e_wire_to_verdict_sets_per_sec" in capsys.readouterr().out


# --- required gated keys (round 6) -------------------------------------------


def test_required_key_gated_across_phase_rename(tmp_path, capsys):
    """The per-set floor moving from a legacy flat key into a phase row
    must STAY gated: base-name matching catches a >3x drop that exact-key
    intersection would silently skip."""
    mod = _load()
    _round(tmp_path, 1, 9000.0,
           extra={"device_sets_per_sec_floor_distinct_pk_and_msg": 3200.0})
    _round(tmp_path, 2, 9000.0, phases={
        "worst_case": {"status": "ok", "rows": {
            "device_sets_per_sec_floor_distinct_pk_and_msg": 800.0,  # 4x drop
        }},
    })
    assert mod.main(["--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "device_sets_per_sec_floor_distinct_pk_and_msg" in out


def test_required_key_missing_fails(tmp_path, capsys):
    """A required row present in the prior round but absent from the
    current one fails the gate — a disappeared row hides regressions as
    effectively as a slow one (the BENCH_r05 lesson)."""
    mod = _load()
    _round(tmp_path, 1, 9000.0,
           extra={"e2e_wire_to_verdict_sets_per_sec": 2000.0})
    _round(tmp_path, 2, 9500.0)  # e2e row gone
    assert mod.main(["--dir", str(tmp_path)]) == 1
    assert "missing from current round" in capsys.readouterr().out


def test_degraded_rounds_are_skipped(tmp_path, capsys):
    """A round that ran with CPU fallbacks / open breaker / armed faults
    (supervisor.degraded — round 7) measures the wrong tier: it must be
    skipped with a note, never gated, in EITHER direction — its terrible
    numbers are not a regression, and a later healthy round recovering
    from them is not a 10x win."""
    mod = _load()
    _round(tmp_path, 1, 9000.0)
    _round(tmp_path, 2, 900.0, extra={  # 10x "drop" — but CPU-tier numbers
        "supervisor": {"degraded": True,
                       "fallbacks": {"breaker_open": 41},
                       "breaker_state": 2},
    })
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "DEGRADED" in out and "nothing to gate" in out
    _round(tmp_path, 3, 8800.0)  # healthy again: compared against r01
    assert mod.main(["--dir", str(tmp_path)]) == 0
    assert "r01 -> r03" in capsys.readouterr().out
    # a degraded details file must not augment the latest healthy round
    details = tmp_path / "bench_details.json"
    details.write_text(json.dumps({
        "metric": "bls_signature_sets_verified_per_sec",
        "value": 700.0,
        "supervisor": {"degraded": True},
    }))
    assert mod.main(["--dir", str(tmp_path), "--details", str(details)]) == 0
    capsys.readouterr()
    # a healthy supervisor section (degraded: false) still gates normally
    _round(tmp_path, 4, 2000.0, extra={  # 4.4x real drop, not degraded
        "supervisor": {"degraded": False, "breaker_state": 0},
    })
    assert mod.main(["--dir", str(tmp_path)]) == 1
    capsys.readouterr()


def test_required_key_improvement_passes(tmp_path, capsys):
    """The round-6 re-bind (e2e_wire_to_verdict now the device-decompress
    default path, ~6x faster) is an IMPROVEMENT and must pass."""
    mod = _load()
    _round(tmp_path, 1, 9000.0,
           extra={"e2e_wire_to_verdict_sets_per_sec": 2042.0})
    _round(tmp_path, 2, 9000.0, phases={
        "e2e": {"status": "ok", "rows": {
            "e2e_wire_to_verdict_sets_per_sec": 12039.0,
        }},
    })
    assert mod.main(["--dir", str(tmp_path)]) == 0
    capsys.readouterr()


# --- timed-out partial flushes (round 7) -------------------------------------


def test_timed_out_rounds_are_skipped_but_logged(tmp_path, capsys):
    """A round the watchdog flushed mid-run (`timed_out: true`, round 7)
    is parseable JSON with real-looking rows — but its rates stopped at
    the deadline. Skippable-but-logged, in either direction: truncated
    numbers gate nothing, and recovery from them is not a win."""
    mod = _load()
    _round(tmp_path, 1, 9000.0)
    _round(tmp_path, 2, 1200.0, extra={  # "7.5x drop" — but partial
        "timed_out": True, "watchdog_fired_after_s": 780.0,
    })
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "timed out mid-run" in out and "nothing to gate" in out
    _round(tmp_path, 3, 8800.0)  # completed again: compared against r01
    assert mod.main(["--dir", str(tmp_path)]) == 0
    assert "r01 -> r03" in capsys.readouterr().out


def test_timed_out_details_do_not_augment(tmp_path, capsys):
    """A timed-out bench_details.json (SIGTERM flush) must not graft its
    partial per-phase rows onto the latest completed round."""
    mod = _load()
    _round(tmp_path, 1, 9000.0,
           extra={"e2e_wire_to_verdict_sets_per_sec": 2000.0})
    _round(tmp_path, 2, 9000.0,
           extra={"e2e_wire_to_verdict_sets_per_sec": 1900.0})
    details = tmp_path / "bench_details.json"
    details.write_text(json.dumps({
        "metric": "bls_signature_sets_verified_per_sec",
        "value": 9000.0,
        "timed_out": True,
        "e2e_wire_to_verdict_sets_per_sec": 300.0,  # partial-run rate
    }))
    assert mod.main(["--dir", str(tmp_path), "--details", str(details)]) == 0
    capsys.readouterr()


def test_empty_history_dir_exits_zero(tmp_path, capsys):
    """A directory with no BENCH files at all (fresh checkout) is a clean
    exit-0 'nothing to gate' — never a traceback."""
    mod = _load()
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "no parseable bench history" in out


def test_null_round_file_is_skipped_not_crashed(tmp_path, capsys):
    """A round file containing JSON `null` (a harness that died while
    writing) must be a logged skip, not an AttributeError."""
    mod = _load()
    (tmp_path / "BENCH_r01.json").write_text("null")
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "not a JSON object" in out
    assert "nothing to gate" in out


def test_non_dict_parsed_is_skipped_not_crashed(tmp_path, capsys):
    """`parsed` holding a string/list (a corrupted emitter document) must
    be a logged skip, not a crash in the timed_out/degraded probes."""
    mod = _load()
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"n": 1, "parsed": "watchdog killed mid-write"})
    )
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"n": 2, "parsed": [1, 2, 3]})
    )
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "`parsed` is not a JSON object" in out


def test_truncated_round_file_is_logged_skip(tmp_path, capsys):
    """Half-written JSON (disk full / kill -9) is an unreadable-file skip
    with the parse error in the note."""
    mod = _load()
    (tmp_path / "BENCH_r01.json").write_text('{"n": 1, "parsed": {')
    (tmp_path / "BENCH_r02.json").write_text("")
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert out.count("unreadable round file") == 2


def test_single_parseable_round_exits_zero(tmp_path, capsys):
    mod = _load()
    _round(tmp_path, 1, 9000.0)
    (tmp_path / "BENCH_r02.json").write_text("null")
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "1 parseable round" in out


# --- SLO verdict gating (ISSUE 16) -------------------------------------------


def _slo(*states):
    """An `slo` bench section with objectives o0..oN in the given states."""
    return {"slo": {"objectives": [
        {"name": f"o{i}", "state": s} for i, s in enumerate(states)
    ]}}


def test_burning_objective_fails_gate_by_name(tmp_path, capsys):
    mod = _load()
    _round(tmp_path, 1, 9000.0, extra=_slo("ok", "ok"))
    _round(tmp_path, 2, 9100.0, extra=_slo("ok", "burning"))
    assert mod.main(["--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    # the verdict delta is printed per objective, and the failure names
    # the burning objective rather than a raw-number diff
    assert "slo:o1  ok -> burning" in out
    assert "BURNING" in out
    assert "slo:o1 (error budget burning)" in out
    assert "error budget" in out.split("FAIL:")[1]


def test_slo_ok_rounds_print_deltas_and_pass(tmp_path, capsys):
    mod = _load()
    _round(tmp_path, 1, 9000.0, extra=_slo("burning", "ok"))
    _round(tmp_path, 2, 9100.0, extra=_slo("ok", "ok"))  # recovered
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "slo:o0  burning -> ok" in out
    assert "OK: no gated key regressed past the threshold" in out


def test_slo_only_mode_gates_exclusively_on_verdicts(tmp_path, capsys):
    mod = _load()
    _round(tmp_path, 1, 9000.0, extra=_slo("ok"))
    _round(tmp_path, 2, 1000.0, extra=_slo("ok"))  # 9x numeric drop
    # the numeric gate fails this history...
    assert mod.main(["--dir", str(tmp_path)]) == 1
    capsys.readouterr()
    # ...but --slo-only judges only the budgets
    assert mod.main(["--dir", str(tmp_path), "--slo-only"]) == 0
    out = capsys.readouterr().out
    assert "numeric thresholds skipped" in out
    assert "OK: no SLO objective is burning its error budget" in out
    _round(tmp_path, 3, 9000.0, extra=_slo("burning"))
    assert mod.main(["--dir", str(tmp_path), "--slo-only"]) == 1
    capsys.readouterr()


def test_rounds_predating_slo_engine_never_gate(tmp_path, capsys):
    """Committed history predates the engine: no `slo` section means no
    verdicts and no gating — in both modes."""
    mod = _load()
    _round(tmp_path, 1, 9000.0)
    _round(tmp_path, 2, 9100.0)
    assert mod.main(["--dir", str(tmp_path)]) == 0
    assert "no SLO verdicts in either round" in capsys.readouterr().out
    assert mod.main(["--dir", str(tmp_path), "--slo-only"]) == 0
    capsys.readouterr()


def test_degraded_and_timed_out_rounds_report_burn_state(tmp_path, capsys):
    """ISSUE 16 satellite: a skipped round still says what its budgets
    looked like when it died (the skip notes themselves are unchanged)."""
    mod = _load()
    _round(tmp_path, 1, 9000.0, extra=_slo("ok"))
    _round(tmp_path, 2, 900.0, extra={
        "supervisor": {"degraded": True, "breaker_state": 2},
        **_slo("burning", "ok"),
    })
    _round(tmp_path, 3, 1200.0, extra={
        "timed_out": True, **_slo("ok", "ok"),
    })
    _round(tmp_path, 4, 8800.0, extra=_slo("ok"))
    assert mod.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "DEGRADED" in out and "timed out mid-run" in out
    assert "r02 burn state — BURNING: o0" in out
    assert "r03 burn state — all 2 objectives ok" in out
    # a skipped round with no slo section reports n/a, not a crash
    _round(tmp_path, 5, 1000.0, extra={"timed_out": True})
    _round(tmp_path, 6, 8700.0, extra=_slo("ok"))
    assert mod.main(["--dir", str(tmp_path)]) == 0
    assert "r05 burn state — n/a" in capsys.readouterr().out


def test_slo_from_details_augments_latest_round(tmp_path, capsys):
    """bench_details.json carries the slo section for the newest round
    when the driver's BENCH_r file predates the engine's emission."""
    mod = _load()
    _round(tmp_path, 1, 9000.0)
    _round(tmp_path, 2, 9100.0)
    details = tmp_path / "bench_details.json"
    details.write_text(json.dumps({
        "metric": "bls_signature_sets_verified_per_sec",
        "value": 9100.0,
        **_slo("ok", "burning"),
    }))
    assert mod.main(["--dir", str(tmp_path), "--details", str(details)]) == 1
    out = capsys.readouterr().out
    assert "slo:o1  n/a -> burning" in out
