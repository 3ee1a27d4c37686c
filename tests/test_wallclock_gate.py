"""wallclock --gate machine-class provenance check.

A perf ratio against a baseline recorded on different hardware is noise
with a threshold attached — loose enough to "pass", it masks real
regressions. The gate must only arm when the baseline's machine-class
provenance matches the runner, and must skip with a reason otherwise.
"""
from __future__ import annotations

import copy

from benchmarks.wallclock import (
    GATE_STEPS,
    GATE_WARMUP,
    MACHINE_CLASS_KEYS,
    gate_skip_reason,
    machine_class,
    machine_info,
    regression_gate,
    resolve_gate_baseline,
    rolling_baseline,
    smoke_section,
)

RUNNER = {
    "platform": "Linux-6.1-x86_64",
    "machine": "x86_64",
    "cpus": 2,
    "python": "3.11.8",
    "jax": "0.9.0",
    "backend": "cpu",
}


def _baseline(machine=None):
    return {
        "machine": machine,
        "smoke": {
            "runs": [
                {
                    "design": "scratchpipe",
                    "scenario": "synthetic",
                    "mode": "sync",
                    "steps_per_s": 10.0,
                }
            ],
            "planner": [],
        },
    }


def test_machine_class_ignores_software_versions():
    other = dict(RUNNER, python="3.12.1", jax="0.5.0",
                 platform="Linux-5.15-x86_64")
    assert machine_class(RUNNER) == machine_class(other)
    assert gate_skip_reason(_baseline(other), current=RUNNER) is None


def test_gate_skips_on_machine_class_mismatch():
    for key, val in (("machine", "aarch64"), ("cpus", 96), ("backend", "tpu")):
        mismatched = dict(RUNNER, **{key: val})
        reason = gate_skip_reason(_baseline(mismatched), current=RUNNER)
        assert reason is not None and key in reason, (key, reason)
        assert "does not match" in reason


def test_gate_skips_on_missing_provenance():
    reason = gate_skip_reason(_baseline(None), current=RUNNER)
    assert reason is not None and "no machine provenance" in reason
    assert gate_skip_reason({}, current=RUNNER) is not None


def test_gate_runs_on_matching_class():
    base = _baseline(copy.deepcopy(RUNNER))
    assert gate_skip_reason(base, current=RUNNER) is None
    fresh = {
        "config": {"warmup": 8, "steps": 10},
        "runs": [
            {
                "design": "scratchpipe",
                "scenario": "synthetic",
                "mode": "sync",
                "steps_per_s": 1.0,  # 10x collapse: must be flagged
            }
        ],
        "planner": [],
    }
    problems = regression_gate(fresh, base, min_ratio=0.35)
    assert problems and "scratchpipe" in problems[0]


def test_gate_skip_reason_defaults_to_current_machine():
    # against the live machine_info() the self-baseline always matches
    assert gate_skip_reason({"machine": machine_info()}) is None
    assert set(MACHINE_CLASS_KEYS) <= set(machine_info())


# ---- rolling baseline (--save-smoke / --gate-fallback) ----------------------
def _tiny_result(machine):
    """A run recorded at gate sizing (what --tiny produces)."""
    return {
        "machine": machine,
        "config": {"warmup": GATE_WARMUP, "steps": GATE_STEPS},
        "runs": [
            {
                "design": "scratchpipe",
                "scenario": "synthetic",
                "mode": "sync",
                "steps_per_s": 9.5,
            }
        ],
        "planner": [],
    }


def test_smoke_section_from_gate_sized_run():
    res = _tiny_result(copy.deepcopy(RUNNER))
    smoke = smoke_section(res)
    assert smoke is not None and smoke["runs"] == res["runs"]
    # a full-sized run without --with-smoke carries no gate-sized section
    full = dict(res, config={"warmup": 40, "steps": 80})
    assert smoke_section(full) is None
    # ... unless it stored one explicitly
    full["smoke"] = {"config": res["config"], "runs": [], "planner": []}
    assert smoke_section(full) == full["smoke"]


def test_rolling_baseline_is_a_valid_gate_baseline():
    roll = rolling_baseline(_tiny_result(copy.deepcopy(RUNNER)))
    assert roll is not None
    # carries provenance and a smoke section — exactly what the gate needs
    assert gate_skip_reason(roll, current=RUNNER) is None
    fresh = _tiny_result(copy.deepcopy(RUNNER))
    fresh["runs"][0]["steps_per_s"] = 0.5  # collapse vs the 9.5 baseline
    problems = regression_gate(fresh, roll, min_ratio=0.35)
    assert problems and "scratchpipe" in problems[0]


def test_resolve_prefers_checked_in_baseline_when_class_matches():
    primary = _baseline(copy.deepcopy(RUNNER))
    fallback = rolling_baseline(_tiny_result(copy.deepcopy(RUNNER)))
    base, skip, notes = resolve_gate_baseline(primary, fallback, current=RUNNER)
    assert base is primary and skip is None and notes == []


def test_resolve_falls_back_to_rolling_baseline():
    other = dict(RUNNER, machine="aarch64")
    primary = _baseline(other)  # recorded on a different machine class
    fallback = rolling_baseline(_tiny_result(copy.deepcopy(RUNNER)))
    base, skip, notes = resolve_gate_baseline(primary, fallback, current=RUNNER)
    assert base is fallback and skip is None
    assert any("checked-in baseline rejected" in n for n in notes)
    assert any("rolling baseline" in n for n in notes)


def test_resolve_skips_when_no_baseline_matches():
    other = dict(RUNNER, machine="aarch64")
    primary = _baseline(other)
    # no fallback at all -> skip with the primary's reason
    base, skip, notes = resolve_gate_baseline(primary, None, current=RUNNER)
    assert base is None and skip is not None
    # fallback from yet another class -> still skip, both rejections noted
    fallback = rolling_baseline(_tiny_result(dict(RUNNER, backend="tpu")))
    base, skip, notes = resolve_gate_baseline(primary, fallback, current=RUNNER)
    assert base is None and skip is not None
    assert sum("rejected" in n for n in notes) == 2
