"""perf_report baseline-compare tests: regression detection, missing
stage, NaN, tolerance boundary, per-stage tolerance overrides, and the
gate verdict (docs/health.md "Perf gate")."""
import importlib.util
import os

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "perf_report",
    os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "perf_report.py"))
perf_report = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(perf_report)


def _report(values):
    return {"schema": 1, "stages": {
        k: {"unit": "ms", "value": v} for k, v in values.items()}}


def _verdict_map(verdicts):
    return {v["stage"]: v["status"] for v in verdicts}


def test_clean_run_passes():
    base = _report({"a": 10.0, "b": 5.0})
    rep = _report({"a": 10.5, "b": 4.2})
    v = perf_report.compare(rep, base, default_tolerance=0.5)
    assert _verdict_map(v) == {"a": "ok", "b": "ok"}
    assert perf_report.gate_verdict(v)


def test_2x_slowdown_trips():
    base = _report({"a": 10.0})
    rep = _report({"a": 20.0})
    v = perf_report.compare(rep, base, default_tolerance=0.5)
    assert _verdict_map(v) == {"a": "regression"}
    assert not perf_report.gate_verdict(v)
    assert v[0]["ratio"] == pytest.approx(2.0)


def test_tolerance_boundary_passes_strictly_above_fails():
    base = _report({"a": 10.0})
    # Exactly 1 + tol: passes (regression is STRICTLY greater).
    v = perf_report.compare(_report({"a": 15.0}), base,
                            default_tolerance=0.5)
    assert _verdict_map(v) == {"a": "ok"}
    v = perf_report.compare(_report({"a": 15.0001}), base,
                            default_tolerance=0.5)
    assert _verdict_map(v) == {"a": "regression"}


def test_improvement_is_ok_not_flagged():
    v = perf_report.compare(_report({"a": 1.0}), _report({"a": 10.0}))
    assert _verdict_map(v) == {"a": "ok"}


def test_missing_stage_fails_gate():
    base = _report({"a": 10.0, "b": 5.0})
    rep = _report({"a": 10.0})
    v = perf_report.compare(rep, base)
    assert _verdict_map(v) == {"a": "ok", "b": "missing"}
    assert not perf_report.gate_verdict(v)


def test_nan_measurement_is_invalid():
    base = _report({"a": 10.0})
    rep = _report({"a": float("nan")})
    v = perf_report.compare(rep, base)
    assert _verdict_map(v) == {"a": "invalid"}
    assert not perf_report.gate_verdict(v)
    # Non-numeric value too.
    rep2 = {"schema": 1, "stages": {"a": {"unit": "ms", "value": "x"}}}
    assert _verdict_map(perf_report.compare(rep2, base)) == {"a": "invalid"}


def test_broken_baseline_is_skipped_not_failed():
    """A NaN/zero/negative baseline entry must not fail every future
    run — it is skipped (and visible as such)."""
    for bad in (float("nan"), 0.0, -1.0, None):
        base = {"schema": 1, "stages": {"a": {"unit": "ms", "value": bad}}}
        v = perf_report.compare(_report({"a": 10.0}), base)
        assert _verdict_map(v) == {"a": "skipped"}
        assert perf_report.gate_verdict(v)


def test_new_stage_is_informational():
    base = _report({"a": 10.0})
    rep = _report({"a": 10.0, "z": 3.0})
    v = perf_report.compare(rep, base)
    assert _verdict_map(v) == {"a": "ok", "z": "new"}
    assert perf_report.gate_verdict(v)


def test_per_stage_tolerance_overrides():
    base = _report({"noisy": 10.0, "tight": 10.0})
    base["tolerances"] = {"noisy": 1.5, "tight": 0.1}
    rep = _report({"noisy": 20.0, "tight": 12.0})
    v = perf_report.compare(rep, base, default_tolerance=0.5)
    assert _verdict_map(v) == {"noisy": "ok", "tight": "regression"}


def test_median():
    assert perf_report._median([3.0]) == 3.0
    assert perf_report._median([1.0, 9.0, 3.0]) == 3.0
    assert perf_report._median([1.0, 3.0]) == 2.0
    assert perf_report._median([]) != perf_report._median([])  # NaN


def test_render_table():
    base = _report({"a": 10.0, "b": 5.0})
    rep = _report({"a": 25.0})
    out = perf_report.render(perf_report.compare(rep, base))
    assert "regression" in out and "missing" in out


def test_committed_baseline_is_loadable_and_complete():
    """The checked-in scripts/perf_baseline.json must stay valid: every stage
    the harness measures is present with a usable value, so the CI
    warn-compare actually compares."""
    import json

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "perf_baseline.json")
    base = json.load(open(path))
    assert base.get("kind") == "horovod_perf_report"
    assert base.get("build", {}).get("version")
    expected = {
        "latency_small_p50_ms", "ring_1mb_ms", "segring_1mb_ms",
        "transport_tcp_4mb_ms", "transport_shm_4mb_ms", "hier_1mb_ms",
        "serving_rtt_p50_ms", "native_ring_16mb_ms",
        "native_off_ring_16mb_ms",
    }
    assert expected <= set(base["stages"]), sorted(base["stages"])
    for name, st in base["stages"].items():
        assert st["value"] > 0, (name, st)
    # Tolerances (if present) must leave a 2x slowdown detectable.
    for name, tol in base.get("tolerances", {}).items():
        assert tol < 1.0, (name, tol)
