"""GPMA incremental sorter + binning: structural invariants and equivalence
with a full rebuild (hypothesis properties live in test_properties.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.binning as binning
import repro.core.gpma as gpma
from repro.core import (
    ResortPolicy,
    SortPolicyConfig,
    build_bins,
    cell_index,
    gpma_update,
    rank_in_runs,
    sort_permutation,
)

N_CELLS = 24
CAP = 16


def check_layout_invariants(layout, cell_ids, alive):
    """Every alive, slotted particle sits in a slot of its own cell's bin;
    slots and particle_slot are mutually consistent; no duplicates."""
    slots = np.asarray(layout.slots)
    pslot = np.asarray(layout.particle_slot)
    cells = np.asarray(cell_ids)
    alive = np.asarray(alive)

    # slot -> particle consistency
    flat = slots.reshape(-1)
    filled = np.nonzero(flat >= 0)[0]
    particles = flat[filled]
    assert len(np.unique(particles)) == len(particles), "duplicate particle in slots"
    np.testing.assert_array_equal(pslot[particles], filled)

    # bin correctness
    bin_of_slot = filled // slots.shape[1]
    np.testing.assert_array_equal(bin_of_slot, cells[particles])

    # alive particles with a slot are exactly the slotted set
    slotted = pslot >= 0
    assert not np.any(slotted & ~alive), "dead particle still slotted"


def test_build_bins_basic():
    cells = jnp.asarray([0, 0, 1, 3, 3, 3, 23], jnp.int32)
    alive = jnp.ones(7, bool)
    layout, overflow = build_bins(cells, alive, n_cells=N_CELLS, capacity=CAP)
    assert int(overflow) == 0
    check_layout_invariants(layout, cells, alive)
    assert int(layout.n_empty()) == N_CELLS * CAP - 7


def test_build_bins_overflow_detected():
    cells = jnp.zeros(CAP + 3, jnp.int32)
    layout, overflow = build_bins(cells, jnp.ones(CAP + 3, bool), n_cells=N_CELLS, capacity=CAP)
    assert int(overflow) == 3
    # the CAP slotted particles are valid
    check_layout_invariants(layout, cells, jnp.asarray(np.asarray(layout.particle_slot) >= 0))


def test_gpma_incremental_matches_rebuild():
    rng = np.random.default_rng(0)
    n = 120
    cells0 = jnp.asarray(rng.integers(0, N_CELLS, n), jnp.int32)
    alive = jnp.ones(n, bool)
    layout, of = build_bins(cells0, alive, n_cells=N_CELLS, capacity=CAP)
    assert int(of) == 0

    # CFL-like motion: ~10% of particles move to a neighboring cell
    move = rng.random(n) < 0.1
    cells1 = np.asarray(cells0).copy()
    cells1[move] = (cells1[move] + rng.integers(1, 3, move.sum())) % N_CELLS
    cells1 = jnp.asarray(cells1)

    new_layout, stats = gpma_update(layout, cells1, alive)
    assert int(stats.n_overflow) == 0
    assert int(stats.n_moved) == int(np.sum(np.asarray(cells0) != cells1))
    check_layout_invariants(new_layout, cells1, alive)


def test_gpma_deaths_free_slots():
    rng = np.random.default_rng(1)
    n = 60
    cells = jnp.asarray(rng.integers(0, N_CELLS, n), jnp.int32)
    layout, _ = build_bins(cells, jnp.ones(n, bool), n_cells=N_CELLS, capacity=CAP)
    alive = jnp.asarray(rng.random(n) > 0.3)
    new_layout, stats = gpma_update(layout, cells, alive)
    check_layout_invariants(new_layout, cells, alive)
    assert int(new_layout.n_empty()) == N_CELLS * CAP - int(alive.sum())


def test_gpma_overflow_flagged_not_lost_silently():
    """When a bin is full, inserts report overflow and unslot the particle."""
    cells0 = jnp.asarray(list(range(CAP)) * 2, jnp.int32)  # spread
    n = cells0.shape[0]
    layout, _ = build_bins(cells0, jnp.ones(n, bool), n_cells=N_CELLS, capacity=CAP)
    # move everyone into cell 0 (capacity CAP < n)
    cells1 = jnp.zeros(n, jnp.int32)
    new_layout, stats = gpma_update(layout, cells1, jnp.ones(n, bool))
    assert int(stats.n_overflow) == n - CAP
    pslot = np.asarray(new_layout.particle_slot)
    assert np.sum(pslot >= 0) == CAP
    check_layout_invariants(new_layout, cells1, jnp.asarray(pslot >= 0))


def _rank_keys(case):
    rng = np.random.default_rng(7)
    if case == "sentinel-run":
        n, n_cells = 5000, 64
        keys = np.where(rng.random(n) < 0.3, rng.integers(0, n_cells, n), n_cells)
    elif case == "all-equal":
        keys = np.full(777, 5)
    elif case == "all-distinct":
        keys = rng.permutation(4096) * 3
    elif case == "one":
        keys = np.asarray([9])
    elif case == "empty":
        keys = np.zeros(0)
    else:  # the bench cells' rank: 2,097,152 keys, 1.5% moved, the rest sentinel
        n, n_cells = 2_097_152, 262_144
        keys = np.where(rng.random(n) < 0.015, rng.integers(0, n_cells, n), n_cells)
    return np.sort(keys).astype(np.int32)


@pytest.mark.parametrize("case", ["sentinel-run", "all-equal", "all-distinct", "one", "empty", "bench-size"])
def test_rank_in_runs_matches_the_search(case):
    """The run-start max-scan gives the within-run rank the binary search gave."""
    keys = _rank_keys(case)
    want = np.arange(keys.size) - np.searchsorted(keys, keys, side="left")
    got = np.asarray(jax.jit(rank_in_runs)(jnp.asarray(keys)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def _search_rank(sorted_key):
    """The rank as it was computed before the max-scan: a binary search."""
    first = jnp.searchsorted(sorted_key, sorted_key, side="left")
    return (jnp.arange(sorted_key.shape[0]) - first).astype(jnp.int32)


@pytest.mark.parametrize(
    "n_cells,capacity,overflows", [(24, 16, True), (64, 48, False)], ids=["overflowing", "roomy"])
def test_rank_scan_gives_the_search_layout(monkeypatch, n_cells, capacity, overflows):
    """`build_bins` and `gpma_update` give the same slots, particle_slot and
    stats with the max-scan rank as with the binary search, through deaths,
    moves and overflow."""
    rng = np.random.default_rng(11)
    n = 2000
    cells0 = jnp.asarray(rng.integers(0, n_cells, n), jnp.int32)
    alive0 = jnp.asarray(rng.random(n) > 0.05)
    move = rng.random(n) < 0.2
    cells1 = jnp.asarray(np.where(move, rng.integers(0, n_cells, n), np.asarray(cells0)), jnp.int32)
    alive1 = alive0 & jnp.asarray(rng.random(n) > 0.05)

    def run(bins, update):
        layout, overflow = bins(cells0, alive0, n_cells=n_cells, capacity=capacity)
        new, stats = update(layout, cells1, alive1)
        return jax.tree.map(np.asarray, (layout, overflow, new, stats))

    got = run(build_bins, gpma_update)
    # the unjitted bodies, which look the rank up when they run
    monkeypatch.setattr(binning, "rank_in_runs", _search_rank)
    monkeypatch.setattr(gpma, "rank_in_runs", _search_rank)
    want = run(build_bins.__wrapped__, gpma_update.__wrapped__)
    assert (int(want[1]) > 0) == overflows and (int(want[3].n_overflow) > 0) == overflows
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_sort_permutation_orders_cells():
    rng = np.random.default_rng(3)
    cells = jnp.asarray(rng.integers(0, N_CELLS, 50), jnp.int32)
    perm = sort_permutation(cells, jnp.ones(50, bool))
    sorted_cells = np.asarray(cells)[np.asarray(perm)]
    assert np.all(np.diff(sorted_cells) >= 0)


def test_resort_policy_triggers():
    pol = ResortPolicy(SortPolicyConfig(sort_interval=50, min_sort_interval=10))
    # min interval wins
    pol.record_step(rebuilt=False)
    assert pol.should_sort(empty_ratio=0.01)[0] is False
    # overflow always wins
    assert pol.should_sort(empty_ratio=0.5, overflowed=True)[0] is True
    # empty-ratio trigger after min interval
    for _ in range(10):
        pol.record_step(rebuilt=False)
    do, reason = pol.should_sort(empty_ratio=0.05)
    assert do and reason == "empty_ratio_low"
    # fixed interval
    pol.reset()
    for _ in range(50):
        pol.record_step(rebuilt=False)
    do, reason = pol.should_sort(empty_ratio=0.5)
    assert do and reason == "fixed_interval"
    # perf degradation
    pol.reset()
    for _ in range(12):
        pol.record_step(rebuilt=False, perf=1.0)
    for _ in range(20):
        pol.record_step(rebuilt=False, perf=0.2)
    do, reason = pol.should_sort(empty_ratio=0.5)
    assert do and reason == "perf_degradation"


def test_gpma_n_moved_counts_unslotted_arrivals_as_moves():
    """Distributed sort-proxy skew regression: a live particle with no slot
    (a migrated-in arrival on the distributed path) is one boundary
    crossing and must count in `n_moved` exactly like a resident particle
    changing cell — otherwise the moved-fraction perf-proxy EMA sees
    different churn on the distributed driver than on the single-device
    one for the same physics."""
    cells0 = jnp.asarray([0, 1, 2, 3], jnp.int32)
    alive0 = jnp.asarray([True, True, True, False], bool)
    layout, of = build_bins(cells0, alive0, n_cells=N_CELLS, capacity=CAP)
    assert int(of) == 0
    assert int(np.asarray(layout.particle_slot)[3]) < 0  # dead slot 3: no bin

    # slot 3 becomes a migrated-in arrival (alive, unslotted) in cell 5;
    # particle 0 moves 0 -> 4; particles 1, 2 stay put
    cells1 = jnp.asarray([4, 1, 2, 5], jnp.int32)
    alive1 = jnp.ones(4, bool)
    new_layout, stats = gpma_update(layout, cells1, alive1)
    assert int(stats.n_moved) == 2, (
        f"expected the resident move AND the arrival to count, got {int(stats.n_moved)}"
    )
    check_layout_invariants(new_layout, cells1, alive1)

    # a stationary step right after: nobody moves, nobody re-counts
    _, stats2 = gpma_update(new_layout, cells1, alive1)
    assert int(stats2.n_moved) == 0


def test_gpma_n_moved_does_not_recount_stuck_overflow_particles():
    """A live particle stuck at particle_slot == -1 against a FULL bin (the
    needs_bins=False incremental configs tolerate overflow indefinitely)
    must not inflate n_moved on every step it waits — only the step its
    insert finally lands counts."""
    cells0 = jnp.zeros(CAP + 2, jnp.int32)  # CAP fit in cell 0, 2 overflow
    alive = jnp.ones(CAP + 2, bool)
    layout, of = build_bins(cells0, alive, n_cells=N_CELLS, capacity=CAP)
    assert int(of) == 2

    # stationary steps: the 2 stuck particles keep failing to insert
    layout1, stats1 = gpma_update(layout, cells0, alive)
    assert int(stats1.n_overflow) == 2
    assert int(stats1.n_moved) == 0, "stuck overflow particles recounted as moves"
    _, stats2 = gpma_update(layout1, cells0, alive)
    assert int(stats2.n_moved) == 0

    # one slotted particle leaves cell 0 -> a gap opens -> exactly one
    # stuck particle lands and is counted, together with the mover
    cells2 = np.asarray(cells0).copy()
    mover = int(np.nonzero(np.asarray(layout1.particle_slot) >= 0)[0][0])
    cells2[mover] = 1
    layout2, stats3 = gpma_update(layout1, jnp.asarray(cells2), alive)
    assert int(stats3.n_moved) == 2  # the mover + the landing straggler
    assert int(stats3.n_overflow) == 1  # one straggler still waiting
    check_layout_invariants(layout2, jnp.asarray(cells2), jnp.asarray(np.asarray(layout2.particle_slot) >= 0))
