"""Perf-regression gate (benchmarks/check_regression.py) unit tests."""
import json

import pytest

from benchmarks.check_regression import check_pair, main, tracked_ratios


def _record(speedups, quick=True, bench="batch_sweep"):
    rec = {"benchmark": bench, "config": {"quick": quick},
           "oo": {"wall_s": 10.0}}
    for name, s in speedups.items():
        rec[name] = {"wall_s": 1.0, "speedup_vs_oo": s}
    return rec


def test_tracked_ratios_found():
    r = _record({"vec": 19.0, "vec_fast": 12.0, "vec_pallas": 5.4})
    assert tracked_ratios(r) == {"vec": 19.0, "vec_fast": 12.0,
                                 "vec_pallas": 5.4}


def test_within_threshold_passes():
    base = _record({"vec": 20.0})
    cur = _record({"vec": 16.0})                 # -20% < 25% threshold
    failures, _ = check_pair(cur, base, 0.25)
    assert failures == []


def test_beyond_threshold_fails():
    base = _record({"vec": 20.0, "vec_fast": 12.0})
    cur = _record({"vec": 14.9, "vec_fast": 12.5})   # vec down 25.5%
    failures, _ = check_pair(cur, base, 0.25)
    assert len(failures) == 1 and "vec" in failures[0]


def test_missing_tracked_key_fails():
    base = _record({"vec": 20.0})
    cur = _record({})
    failures, _ = check_pair(cur, base, 0.25)
    assert failures and "missing" in failures[0]


def test_new_flavour_without_baseline_is_note_not_failure():
    base = _record({"vec": 20.0})
    cur = _record({"vec": 20.0, "vec_gpu": 100.0})
    failures, notes = check_pair(cur, base, 0.25)
    assert failures == []
    assert any("vec_gpu" in n for n in notes)


def test_quick_mode_mismatch_noted():
    base = _record({"vec": 20.0}, quick=False)
    cur = _record({"vec": 20.0}, quick=True)
    _, notes = check_pair(cur, base, 0.25)
    assert any("quick-mode mismatch" in n for n in notes)


def test_device_count_mismatch_not_gated():
    """Speedups are only comparable like-for-like by device count: an
    8-device record never gates (pass or fail) against a 1-device baseline."""
    base = _record({"vec": 20.0})
    cur = _record({"vec": 5.0})                  # would fail hard...
    base["vec"]["devices"], cur["vec"]["devices"] = 1, 8
    failures, notes = check_pair(cur, base, 0.25)
    assert failures == []                        # ...but is skipped
    assert any("device-count mismatch" in n for n in notes)


def test_device_count_match_still_gates():
    base = _record({"vec": 20.0})
    cur = _record({"vec": 5.0})
    base["vec"]["devices"] = cur["vec"]["devices"] = 1
    failures, _ = check_pair(cur, base, 0.25)
    assert len(failures) == 1


def test_baseline_key_absent_from_current_section_fails():
    """A metric rename must surface as 'missing', not silently gate the
    section's other (semantically different) tracked ratio."""
    base = {"benchmark": "b", "config": {"quick": True},
            "vec": {"speedup_vs_oo": 20.0}}
    cur = {"benchmark": "b", "config": {"quick": True},
           "vec": {"speedup_vs_monolithic": 19.5}}
    failures, _ = check_pair(cur, base, 0.25)
    assert len(failures) == 1 and "missing" in failures[0]


def test_speedup_vs_monolithic_sections_tracked():
    """The sweep_runner record's tracked key gates like speedup_vs_oo."""
    base = {"benchmark": "sweep_runner", "config": {"quick": True},
            "sweep": {"speedup_vs_monolithic": 2.0, "devices": 1}}
    cur = {"benchmark": "sweep_runner", "config": {"quick": True},
           "sweep": {"speedup_vs_monolithic": 1.0, "devices": 1}}
    failures, _ = check_pair(cur, base, 0.25)
    assert len(failures) == 1 and "speedup_vs_monolithic" in failures[0]
    failures, _ = check_pair(base, base, 0.25)
    assert failures == []


def test_speedup_vs_bucketed_sections_tracked():
    """The compaction record's tracked key gates like speedup_vs_oo."""
    base = {"benchmark": "compaction_sweep", "config": {"quick": True},
            "compact": {"speedup_vs_bucketed": 2.0, "devices": 1}}
    cur = {"benchmark": "compaction_sweep", "config": {"quick": True},
           "compact": {"speedup_vs_bucketed": 1.0, "devices": 1}}
    failures, _ = check_pair(cur, base, 0.25)
    assert len(failures) == 1 and "speedup_vs_bucketed" in failures[0]
    failures, _ = check_pair(base, base, 0.25)
    assert failures == []


def _rate_record(eps, frac=0.97, devices=1, compacted=True):
    return {"benchmark": "compaction_sweep", "config": {"quick": True},
            "compact": {"events_per_s": eps, "devices": devices,
                        "compacted": compacted,
                        "observed_active_lane_fraction": frac}}


def test_events_per_s_gated_as_ratio():
    base = _rate_record(1_000_000.0)
    ok, _ = check_pair(_rate_record(800_000.0), base, 0.25)
    assert ok == []                              # -20% within threshold
    bad, _ = check_pair(_rate_record(700_000.0), base, 0.25)
    assert len(bad) == 1 and "events_per_s" in bad[0]


def test_events_per_s_missing_from_current_fails():
    base = _rate_record(1_000_000.0)
    cur = _rate_record(1_000_000.0)
    del cur["compact"]["events_per_s"]
    failures, _ = check_pair(cur, base, 0.25)
    assert failures and "events_per_s missing" in failures[0]


def test_events_per_s_device_mismatch_not_gated():
    base = _rate_record(1_000_000.0, devices=1)
    cur = _rate_record(100_000.0, devices=8)
    failures, notes = check_pair(cur, base, 0.25)
    assert failures == []
    assert any("events_per_s not gated" in n for n in notes)


def test_events_per_s_without_fraction_field_not_gated():
    """Ad-hoc events_per_s figures in older records stay ungated: the rate
    gate is scoped to sections written via _util.report_fields."""
    base = _rate_record(1_000_000.0)
    cur = _rate_record(100_000.0)
    for rec in (base, cur):
        del rec["compact"]["observed_active_lane_fraction"]
    failures, _ = check_pair(cur, base, 0.25)
    assert failures == []


def _kernel_record(eps, native=False):
    return {"benchmark": "kernel_bench", "config": {"quick": True},
            "step_power": {"events_per_s": eps, "pallas_native": native,
                           "bit_exact_vs_plain": True}}


def test_kernel_rate_sections_gated():
    """BENCH_kernels.json sections (events_per_s + pallas_native, no
    fraction field) are in the rate gate's scope."""
    base = _kernel_record(100_000.0)
    ok, _ = check_pair(_kernel_record(80_000.0), base, 0.25)
    assert ok == []
    bad, _ = check_pair(_kernel_record(60_000.0), base, 0.25)
    assert len(bad) == 1 and "events_per_s" in bad[0]


def test_kernel_rate_native_mismatch_not_gated():
    """An interpret-mode CPU rate is never held to a natively lowered
    baseline (or vice versa) — the rate measures the runner, not the
    kernel."""
    base = _kernel_record(10_000_000.0, native=True)
    failures, notes = check_pair(_kernel_record(100_000.0, native=False),
                                 base, 0.25)
    assert failures == []
    assert any("pallas_native mismatch" in n for n in notes)


def test_compacted_fraction_floor():
    """A compacted section below 0.95 observed occupancy fails outright —
    an absolute floor, independent of any baseline value."""
    base = _rate_record(1_000_000.0, frac=0.97)
    bad, _ = check_pair(_rate_record(1_000_000.0, frac=0.93), base, 0.25)
    assert any("below absolute floor" in f for f in bad)
    ok, notes = check_pair(_rate_record(1_000_000.0, frac=0.96), base, 0.25)
    assert ok == []
    assert any("floor" in n for n in notes)


def test_fraction_floor_skips_uncompacted_sections():
    base = _rate_record(1_000_000.0, frac=0.5, compacted=False)
    failures, _ = check_pair(_rate_record(1_000_000.0, frac=0.5,
                                          compacted=False), base, 0.25)
    assert failures == []


def test_cli_exit_codes(tmp_path):
    """Acceptance: the CLI exits non-zero on a >25% speedup degradation."""
    base = tmp_path / "base.json"
    cur_ok = tmp_path / "ok.json"
    cur_bad = tmp_path / "bad.json"
    base.write_text(json.dumps(_record({"vec": 20.0})))
    cur_ok.write_text(json.dumps(_record({"vec": 19.0})))
    cur_bad.write_text(json.dumps(_record({"vec": 10.0})))
    assert main([str(cur_ok), str(base)]) == 0
    assert main([str(cur_bad), str(base)]) == 1
    # custom threshold: a 50% drop passes a 60% threshold
    assert main([str(cur_bad), str(base), "--threshold", "0.6"]) == 0


def test_cli_missing_baseline_skips(tmp_path, capsys):
    cur = tmp_path / "cur.json"
    cur.write_text(json.dumps(_record({"vec": 20.0})))
    assert main([str(cur), str(tmp_path / "nope.json")]) == 0
    assert "skipping gate" in capsys.readouterr().out


def test_cli_missing_current_fails(tmp_path):
    base = tmp_path / "base.json"
    base.write_text(json.dumps(_record({"vec": 20.0})))
    assert main([str(tmp_path / "nope.json"), str(base)]) == 1


def test_committed_baselines_are_consistent():
    """The baselines shipped in-repo parse and carry tracked ratios."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    for name in ("substrate.json", "substrate_quick.json",
                 "workflow.json", "workflow_quick.json",
                 "sweep.json", "sweep_quick.json",
                 "compaction.json", "compaction_quick.json"):
        rec = json.loads((root / "benchmarks" / "baselines" / name)
                         .read_text())
        assert tracked_ratios(rec), name
        assert rec["config"]["quick"] == name.endswith("_quick.json"), name
