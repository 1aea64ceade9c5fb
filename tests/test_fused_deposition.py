"""Fused three-component deposition megakernel: correctness coverage.

The contract (ISSUE 1 acceptance): the fused path must be bit-comparable
(<= 1e-5 fp32) to three independent per-component `deposit_matrix` calls,
within oracle tolerance of the float64 `deposit_scatter` oracle, and robust
to non-cubic grids, empty bins, and overflowed particles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CURRENT_STAGGER,
    build_bins,
    cell_index,
    choose_capacity,
    deposit_current_matrix_fused,
    deposit_matrix,
    deposit_scatter,
    fused_bin_slab,
    shape_weights,
    shape_weights_window,
    support,
    unified_support,
)
from repro.kernels.deposition import fused_bin_deposit, fused_bin_deposit_ref

ORDERS = [1, 2, 3]
GRIDS = [(6, 5, 4), (3, 8, 5)]  # non-cubic, mutually non-divisible extents


def make_binned(pos, grid_shape, *, capacity=None):
    n = pos.shape[0]
    cells = cell_index(pos, grid_shape)
    n_cells = int(np.prod(grid_shape))
    if capacity is None:
        capacity = choose_capacity(int(np.max(np.bincount(np.asarray(cells), minlength=n_cells))))
    return build_bins(cells, jnp.ones(n, bool), n_cells=n_cells, capacity=capacity)


def make_particles(n, grid_shape, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    pos = jax.random.uniform(k1, (n, 3)) * jnp.asarray(grid_shape, jnp.float32)
    vel = jax.random.normal(k2, (n, 3))
    qw = jax.random.uniform(k3, (n,), minval=0.5, maxval=1.5)
    return pos, vel, qw


@pytest.mark.parametrize("order", ORDERS)
def test_unified_window_covers_both_staggers(order):
    t, base = unified_support(order)
    for staggered in (False, True):
        nt, b = support(order, staggered)
        assert base <= b and b + nt <= base + t


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("staggered", [False, True])
def test_window_weights_zero_pad_support_weights(order, staggered):
    """Unified-window weights == SUPPORT-window weights, zero-padded."""
    d = jnp.linspace(0.0, 0.999, 53)
    t, base = unified_support(order)
    nt, b = support(order, staggered)
    wide = np.asarray(shape_weights_window(d, order, staggered, n_taps=t, base=base))
    narrow = np.asarray(shape_weights(d, order, staggered))
    lo = b - base
    np.testing.assert_allclose(wide[:, lo : lo + nt], narrow, atol=0)
    mask = np.ones(t, bool)
    mask[lo : lo + nt] = False
    np.testing.assert_allclose(wide[:, mask], 0.0, atol=0)


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("grid", GRIDS)
def test_fused_matches_per_component_matrix(order, grid):
    """Fused megakernel path == three independent deposit_matrix calls."""
    pos, vel, qw = make_particles(500, grid, seed=order)
    layout, of = make_binned(pos, grid)
    assert int(of) == 0

    fused = deposit_current_matrix_fused(pos, vel, qw, layout, grid_shape=grid, order=order)
    fused_pl = deposit_current_matrix_fused(
        pos, vel, qw, layout, grid_shape=grid, order=order, fused_matmul=fused_bin_deposit
    )
    for comp in range(3):
        per_comp = deposit_matrix(
            pos, qw * vel[:, comp], layout, grid_shape=grid, order=order,
            stagger=CURRENT_STAGGER[comp],
        )
        np.testing.assert_allclose(
            np.asarray(fused[comp]), np.asarray(per_comp), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(fused_pl[comp]), np.asarray(fused[comp]), rtol=1e-5, atol=1e-5
        )


@pytest.mark.parametrize("order", ORDERS)
def test_fused_vs_float64_scatter_oracle(order):
    grid = (6, 5, 4)
    pos, vel, qw = make_particles(800, grid, seed=7)
    layout, _ = make_binned(pos, grid)
    fused = deposit_current_matrix_fused(pos, vel, qw, layout, grid_shape=grid, order=order)

    with jax.enable_x64(True):
        for comp in range(3):
            ref64 = deposit_scatter(
                jnp.asarray(np.asarray(pos), jnp.float64),
                jnp.asarray(np.asarray(qw * vel[:, comp]), jnp.float64),
                grid_shape=grid,
                order=order,
                stagger=CURRENT_STAGGER[comp],
            )
            scale = float(np.abs(np.asarray(ref64)).max())
            err = float(np.abs(np.asarray(fused[comp], np.float64) - np.asarray(ref64)).max())
            assert err / scale < 1e-5


@pytest.mark.parametrize("order", [1, 3])
def test_fused_with_empty_bins(order):
    """Particles clustered in one corner cell: almost every bin is empty."""
    grid = (5, 4, 6)
    k = jax.random.PRNGKey(3)
    pos = jax.random.uniform(k, (64, 3)) * 0.9 + 0.05  # all inside cell (0,0,0)
    vel = jnp.ones((64, 3))
    qw = jnp.full((64,), 0.5)
    layout, of = make_binned(pos, grid, capacity=choose_capacity(64))
    assert int(of) == 0
    fused = deposit_current_matrix_fused(pos, vel, qw, layout, grid_shape=grid, order=order)
    fused_pl = deposit_current_matrix_fused(
        pos, vel, qw, layout, grid_shape=grid, order=order, fused_matmul=fused_bin_deposit
    )
    for comp in range(3):
        want = deposit_scatter(
            pos, qw * vel[:, comp], grid_shape=grid, order=order, stagger=CURRENT_STAGGER[comp]
        )
        np.testing.assert_allclose(np.asarray(fused[comp]), np.asarray(want), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fused_pl[comp]), np.asarray(fused[comp]), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("order", [1, 2])
def test_fused_with_overflowed_particles(order):
    """Overflowed (unslotted) particles are dropped identically by the fused
    and the per-component matrix paths."""
    grid = (4, 4, 4)
    pos, vel, qw = make_particles(600, grid, seed=11)
    layout, of = make_binned(pos, grid, capacity=8)  # 600/64 ≈ 9.4 ppc: overflows
    assert int(of) > 0

    fused = deposit_current_matrix_fused(pos, vel, qw, layout, grid_shape=grid, order=order)
    for comp in range(3):
        per_comp = deposit_matrix(
            pos, qw * vel[:, comp], layout, grid_shape=grid, order=order,
            stagger=CURRENT_STAGGER[comp],
        )
        np.testing.assert_allclose(
            np.asarray(fused[comp]), np.asarray(per_comp), rtol=1e-5, atol=1e-5
        )
    # and the dropped charge is visible vs the full scatter (sanity that the
    # overflow case actually exercised a different path)
    full = deposit_scatter(pos, qw * vel[:, 0], grid_shape=grid, order=order, stagger=CURRENT_STAGGER[0])
    assert not np.allclose(np.asarray(fused[0]), np.asarray(full), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("order", ORDERS)
def test_fused_kernel_matches_ref_ragged_blocks(order):
    """Raw megakernel vs jnp oracle with a block size that doesn't divide C."""
    c, cap = 37, 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(order))
    # binning guarantees d in [0, 1); the widened SUPPORT windows only
    # zero-pad the unified window on that domain
    d = jax.random.uniform(k1, (c, cap, 3), minval=0.0, maxval=0.999)
    val = jax.random.normal(k2, (c, cap, 3))
    got = fused_bin_deposit(d, val, order=order, block_cells=7)
    want = fused_bin_deposit_ref(d, val, order=order)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_fused_bin_slab_masks_gaps():
    grid = (4, 3, 5)
    pos, vel, qw = make_particles(100, grid, seed=5)
    layout, _ = make_binned(pos, grid)
    d, val = fused_bin_slab(pos, vel, qw, layout, grid_shape=grid)
    assert d.shape == (int(np.prod(grid)), layout.capacity, 3)
    assert val.shape == d.shape
    gaps = ~np.asarray(layout.valid_mask())
    np.testing.assert_allclose(np.asarray(val)[gaps], 0.0, atol=0)


def test_simulation_fused_matches_unfused():
    """One pic_step with deposition="matrix" (fused) vs "matrix_unfused"."""
    import dataclasses

    from repro.pic import FieldState, GridSpec, PICConfig, Simulation, uniform_plasma

    grid = GridSpec(shape=(6, 6, 6))
    parts = uniform_plasma(jax.random.PRNGKey(0), grid, ppc_each_dim=(2, 2, 2), density=1.0, u_thermal=0.05)
    fields = FieldState.zeros(grid.shape)
    results = {}
    for dep in ("matrix", "matrix_unfused"):
        cfg = PICConfig(grid=grid, dt=0.2, order=2, deposition=dep, gather="matrix", capacity=16)
        sim = Simulation(fields, dataclasses.replace(parts), cfg)
        sim.run(3)
        results[dep] = np.stack([np.asarray(f) for f in sim.state.fields.e()])
    np.testing.assert_allclose(results["matrix"], results["matrix_unfused"], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("order", ORDERS)
@pytest.mark.parametrize("grid", GRIDS)
def test_fused_staging_bit_identical_to_two_gathers(order, grid):
    """`bin_slab_staging` (ONE slot gather for positions + values, the PR 5
    carried-forward follow-up) is BITWISE identical to the historical
    `build_bin_slab` + `bin_slab_values` two-gather route, and feeding its
    values slab into the fused deposit reproduces the internal path."""
    from repro.core import bin_slab_staging, bin_slab_values, build_bin_slab

    pos, vel, qw = make_particles(400, grid, seed=10 + order)
    layout, of = make_binned(pos, grid)
    assert int(of) == 0

    slab_ref = build_bin_slab(pos, layout, grid_shape=grid)
    values_ref = bin_slab_values(vel, qw, layout, slab_ref)
    slab, values = bin_slab_staging(pos, vel, qw, layout, grid_shape=grid)

    np.testing.assert_array_equal(np.asarray(slab.valid), np.asarray(slab_ref.valid))
    np.testing.assert_array_equal(np.asarray(slab.d), np.asarray(slab_ref.d))
    np.testing.assert_array_equal(np.asarray(values), np.asarray(values_ref))

    internal = deposit_current_matrix_fused(
        pos, vel, qw, layout, grid_shape=grid, order=order, slab=slab_ref
    )
    via_values = deposit_current_matrix_fused(
        pos, vel, qw, layout, grid_shape=grid, order=order, slab=slab, values=values
    )
    for comp in range(3):
        np.testing.assert_array_equal(
            np.asarray(via_values[comp]), np.asarray(internal[comp])
        )
