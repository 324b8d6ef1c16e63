"""bench.py phase-budget invariant (ISSUE 4 satellite — the r05 rc=124
post-mortem class of bug): phase budgets are carved from the remaining
global budget, so no sequence of phases can ever be ALLOWED to spend past
TOTAL_BUDGET_S — the driver's hard kill can then never land before the
bench's own watchdog flushes the artifact."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402


def _simulate(nominals, total, reserve):
    """Carve each phase's budget from the simulated remaining budget and
    let the phase consume ALL of it (the worst case the clamp must bound).
    Returns (per-phase budgets, total spend)."""
    remaining = total
    budgets = []
    for nominal in nominals:
        b = bench.phase_budget(nominal, remaining_s=remaining,
                               reserve_s=reserve)
        assert b >= 0.0
        assert b <= nominal
        budgets.append(b)
        remaining -= b  # phase runs to its full allowance
    return budgets, total - remaining


def test_budgets_never_sum_past_global_budget():
    rng = np.random.default_rng(42)
    for _ in range(200):
        n = int(rng.integers(1, 12))
        nominals = rng.uniform(10.0, 2000.0, n).tolist()
        total = float(rng.uniform(30.0, 1200.0))
        reserve = float(rng.uniform(0.0, 30.0))
        budgets, spent = _simulate(nominals, total, reserve)
        assert spent <= total + 1e-9, (nominals, total, budgets)


def test_exhausted_budget_yields_zero():
    assert bench.phase_budget(600.0, remaining_s=10.0, reserve_s=15.0) == 0.0
    assert bench.phase_budget(600.0, remaining_s=-5.0) == 0.0


def test_reserve_is_kept_for_the_artifact_flush():
    # a phase can never be granted the final reserve_s of the budget
    b = bench.phase_budget(10_000.0, remaining_s=100.0, reserve_s=15.0)
    assert b == 85.0


def test_bench_registry_includes_multi_rule_shared():
    """The new phase is wired into main()'s budgeted phase table."""
    import inspect

    src = inspect.getsource(bench.main)
    assert "multi_rule_shared" in src
    assert "phase_budget" in src


# ------------------------------------------------- phase floors (r05 fix)
def test_floors_fit_the_global_budget():
    """The roster's floors plus the flush reserve must fit TOTAL_BUDGET_S
    with slack — otherwise the floor guarantee below is vacuous."""
    total_floor = sum(f for _, f in bench.PHASE_FLOORS)
    assert total_floor + 30.0 < bench.TOTAL_BUDGET_S, (
        f"floors sum to {total_floor}s against a "
        f"{bench.TOTAL_BUDGET_S}s budget")
    assert all(f > 0 for _, f in bench.PHASE_FLOORS)


def test_later_floor_sums_the_tail():
    names = [n for n, _ in bench.PHASE_FLOORS]
    assert bench.later_floor(names[-1]) == 0.0
    assert bench.later_floor(names[0]) == sum(
        f for _, f in bench.PHASE_FLOORS[1:])
    # ad-hoc tags outside the roster get the plain greedy carve
    assert bench.later_floor("not-a-phase") == 0.0


def test_greedy_phase_cannot_starve_the_roster():
    """THE r05 regression: full_pipe alone was allowed the whole 900s, so
    nothing after it ever ran. With floors, even when every phase asks
    for (and spends) its maximum, every later phase is still offered at
    least its floor."""
    remaining = bench.TOTAL_BUDGET_S
    reserve = 15.0
    for tag, floor in bench.PHASE_FLOORS:
        b = bench.phase_budget(10_000.0, remaining_s=remaining,
                               reserve_s=reserve,
                               later_floor_s=bench.later_floor(tag))
        assert b >= floor - 1e-9, (
            f"{tag} offered {b:.1f}s < its {floor:.0f}s floor")
        remaining -= b  # worst case: the phase spends everything offered
    assert remaining >= reserve - 1e-9  # the final-JSON flush survives


def test_floors_still_respect_the_global_cap():
    """Floors carve opportunity, never extra spend: the summed grants
    stay within the global budget for random spend patterns too."""
    rng = np.random.default_rng(7)
    for _ in range(100):
        remaining = total = float(rng.uniform(100.0, 1200.0))
        spent = 0.0
        for tag, _ in bench.PHASE_FLOORS:
            b = bench.phase_budget(
                float(rng.uniform(10.0, 2000.0)), remaining_s=remaining,
                reserve_s=15.0, later_floor_s=bench.later_floor(tag))
            use = b * float(rng.uniform(0.0, 1.0))
            spent += use
            remaining -= use
        assert spent <= total + 1e-9


def test_block_marker_tolerates_donation_only():
    """The pacing marker skips donated/deleted state buffers (CPU jax
    honors donate_argnums — blocking one raises) but a real device fault
    must still propagate, or the loop loses its in-flight bound."""

    class Deleted:
        def is_deleted(self):
            return True

    bench._block_marker(None)
    bench._block_marker(Deleted())  # donated: silently skipped

    class DonationRace:
        def is_deleted(self):
            raise RuntimeError(
                "BlockHostUntilReady() called on deleted or donated buffer")

    bench._block_marker(DonationRace())  # the benign race class

    class LinkFault:
        def is_deleted(self):
            raise RuntimeError("socket closed")

    import pytest

    with pytest.raises(RuntimeError, match="socket closed"):
        bench._block_marker(LinkFault())


# -------------------------------------- child watchdog dump harvest (r05)
def test_flush_record_dump_roundtrips_through_harvest(capsys):
    """A killed child's dying `#R` dump must restore its phases into the
    parent's RESULTS — the exact r05 failure (child exceeded the
    watchdog, stdout JSON discarded, artifact `parsed` came back null)."""
    saved = dict(bench.RESULTS)
    try:
        bench.RESULTS.clear()
        bench.RESULTS["full_pipe"] = {"rows_per_sec": 1.0e6,
                                      "e2e_p99_ms": 4.0}
        bench.RESULTS["full_pipe_error"] = "watchdog: exceeded 500s"
        bench._flush_record_dump()
        child_stderr = capsys.readouterr().err
        assert child_stderr.startswith("#R ")
        # the parent re-parses the child's stderr after the kill
        bench.RESULTS.clear()
        bench._harvest_phase_stderr(child_stderr, "full-pipe")
        assert bench.RESULTS["full_pipe"]["rows_per_sec"] == 1.0e6
        assert "watchdog" in bench.RESULTS["full_pipe_error"]
    finally:
        bench.RESULTS.clear()
        bench.RESULTS.update(saved)


def test_flush_record_dump_survives_unserializable_entries(capsys):
    """The dying gasp must never throw — a bad RESULTS entry degrades to
    no dump line, not a crash in the watchdog thread."""
    saved = dict(bench.RESULTS)
    try:
        bench.RESULTS.clear()
        bench.RESULTS["bad"] = object()  # not JSON-serializable
        bench._flush_record_dump()  # must not raise
        capsys.readouterr()
    finally:
        bench.RESULTS.clear()
        bench.RESULTS.update(saved)
