"""Core deposition: the three implementations must agree to fp32 accuracy,
and shape functions must satisfy B-spline invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    build_bins,
    cell_index,
    choose_capacity,
    deposit_matrix,
    deposit_rhocell,
    deposit_scatter,
    fold_guards,
    gather_matrix,
    gather_scatter,
    max_guard,
    shape_weights,
    unfold_guards,
)
from repro.core.deposition import NO_STAGGER, STAGGER_X, STAGGER_Y, STAGGER_Z

GRID = (6, 5, 4)
STAGGERS = [NO_STAGGER, STAGGER_X, STAGGER_Y, STAGGER_Z]


def make_particles(n, grid_shape, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    dims = jnp.asarray(grid_shape, jnp.float32)
    pos = jax.random.uniform(k1, (n, 3)) * dims
    vel = jax.random.normal(k2, (n, 3))
    qw = jax.random.uniform(k3, (n,), minval=0.5, maxval=1.5)
    return pos, vel, qw


@pytest.mark.parametrize("order", [1, 2, 3])
@pytest.mark.parametrize("staggered", [False, True])
def test_shape_weights_partition_of_unity(order, staggered):
    d = jnp.linspace(0.0, 0.999, 101)
    w = shape_weights(d, order, staggered)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    assert np.all(np.asarray(w) >= -1e-7)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_cic_matches_closed_form(order):
    # order-1 unstaggered weights are [1-d, d]
    if order == 1:
        w = shape_weights(jnp.asarray([0.25]), 1, False)
        np.testing.assert_allclose(np.asarray(w[0]), [0.75, 0.25], atol=1e-7)
    # taps outside true support are exactly zero
    w = shape_weights(jnp.asarray([0.0, 0.5, 0.99]), order, True)
    assert np.asarray(w).shape[-1] == shape_weights(jnp.zeros(1), order, True).shape[-1]


@pytest.mark.parametrize("order", [1, 2, 3])
@pytest.mark.parametrize("stagger", STAGGERS)
def test_three_deposition_methods_agree(order, stagger):
    pos, vel, qw = make_particles(512, GRID)
    values = qw * vel[:, 0]
    cells = cell_index(pos, GRID)
    n_cells = int(np.prod(GRID))
    cap = choose_capacity(int(np.max(np.bincount(np.asarray(cells), minlength=n_cells))))
    layout, overflow = build_bins(cells, jnp.ones(pos.shape[0], bool), n_cells=n_cells, capacity=cap)
    assert int(overflow) == 0

    ref = deposit_scatter(pos, values, grid_shape=GRID, order=order, stagger=stagger)
    rc = deposit_rhocell(pos, values, cells, grid_shape=GRID, order=order, stagger=stagger)
    mx = deposit_matrix(pos, values, layout, grid_shape=GRID, order=order, stagger=stagger)
    mx_direct = deposit_matrix(
        pos, values, layout, grid_shape=GRID, order=order, stagger=stagger, separable_reduce=False
    )

    np.testing.assert_allclose(np.asarray(rc), np.asarray(ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mx), np.asarray(ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mx_direct), np.asarray(mx), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_total_charge_conserved(order):
    """Partition of unity => sum over grid == sum of particle values."""
    pos, vel, qw = make_particles(256, GRID, seed=1)
    padded = deposit_scatter(pos, qw, grid_shape=GRID, order=order)
    total = fold_guards(padded, max_guard(order)).sum()
    np.testing.assert_allclose(float(total), float(qw.sum()), rtol=1e-5)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_matrix_vs_float64_oracle(order):
    """fp32 matrix deposition vs float64 scatter oracle: rel error < 1e-5."""
    pos, vel, qw = make_particles(1024, GRID, seed=2)
    values = qw * vel[:, 1]
    cells = cell_index(pos, GRID)
    n_cells = int(np.prod(GRID))
    cap = choose_capacity(int(np.max(np.bincount(np.asarray(cells), minlength=n_cells))))
    layout, _ = build_bins(cells, jnp.ones(pos.shape[0], bool), n_cells=n_cells, capacity=cap)
    mx = deposit_matrix(pos, values, layout, grid_shape=GRID, order=order)

    with jax.enable_x64(True):
        ref64 = deposit_scatter(
            jnp.asarray(np.asarray(pos), jnp.float64),
            jnp.asarray(np.asarray(values), jnp.float64),
            grid_shape=GRID,
            order=order,
        )
        scale = float(np.abs(np.asarray(ref64)).max())
        err = float(np.abs(np.asarray(mx, np.float64) - np.asarray(ref64)).max())
    assert err / scale < 1e-5


@pytest.mark.parametrize("order", [1, 3])
@pytest.mark.parametrize("stagger", [NO_STAGGER, STAGGER_X])
def test_gather_matrix_matches_scatter_gather(order, stagger):
    pos, _, _ = make_particles(300, GRID, seed=3)
    cells = cell_index(pos, GRID)
    n_cells = int(np.prod(GRID))
    cap = choose_capacity(int(np.max(np.bincount(np.asarray(cells), minlength=n_cells))))
    layout, _ = build_bins(cells, jnp.ones(pos.shape[0], bool), n_cells=n_cells, capacity=cap)

    g = max_guard(order)
    field = jax.random.normal(jax.random.PRNGKey(7), GRID)
    padded = unfold_guards(field, g)

    ref = gather_scatter(pos, padded, order=order, stagger=stagger)
    mat = gather_matrix(pos, padded, layout, grid_shape=GRID, order=order, stagger=stagger)
    np.testing.assert_allclose(np.asarray(mat), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_fold_unfold_roundtrip():
    field = jax.random.normal(jax.random.PRNGKey(0), GRID)
    padded = unfold_guards(field, 2)
    # folding a periodic-padded field double counts the wrapped cells; instead
    # check shape and that an empty-guard pad folds to identity.
    assert padded.shape == tuple(s + 4 for s in GRID)
    zero_pad = jnp.zeros_like(padded).at[2:-2, 2:-2, 2:-2].set(field)
    np.testing.assert_allclose(np.asarray(fold_guards(zero_pad, 2)), np.asarray(field), atol=0)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_fused_current_deposition_matches_scatter(order):
    """deposit_current_matrix_fused (§Perf P2) == per-component scatter."""
    from repro.core import deposit_current_matrix_fused

    pos, vel, qw_ = make_particles(400, GRID, seed=5)
    cells = cell_index(pos, GRID)
    n_cells = int(np.prod(GRID))
    cap = choose_capacity(int(np.max(np.bincount(np.asarray(cells), minlength=n_cells))))
    layout, _ = build_bins(cells, jnp.ones(400, bool), n_cells=n_cells, capacity=cap)
    got = deposit_current_matrix_fused(pos, vel, qw_, layout, grid_shape=GRID, order=order)
    for comp, stagger in enumerate(STAGGERS[1:]):
        want = deposit_scatter(pos, qw_ * vel[:, comp], grid_shape=GRID, order=order, stagger=stagger)
        np.testing.assert_allclose(np.asarray(got[comp]), np.asarray(want), rtol=1e-5, atol=1e-5)
