"""Fused six-component field gather + BinSlab staging: oracle parity across
all six staggered components (orders 1-3, non-cubic grids, empty bins, dead
and unslotted particles), fused == six-call equivalence, sim-level pinning,
backend config resolution, and the structural one-slab-per-step
guarantee. (Pallas-vs-ref kernel parity lives in test_kernels.py.)"""

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.binning as binning
from repro.core import (
    EB_STAGGERS,
    build_bin_slab,
    build_bins,
    cell_index,
    choose_capacity,
    gather_fields_fused,
    gather_matrix,
    gather_scatter,
    max_guard,
    unfold_guards,
)
from repro.pic import B_STAGGER, E_STAGGER, FieldState, GridSpec, PICConfig, Simulation, uniform_plasma
from repro.pic.simulation import _pic_step

GRID = (6, 5, 4)


def _ignore_deprecation(fn):
    def wrapped(*a, **kw):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return fn(*a, **kw)

    return wrapped


Simulation = _ignore_deprecation(Simulation)


def make_workload(n, grid_shape, *, seed=0, capacity=None, n_dead=0, n_crowded=0):
    """Particles (some dead), six random field components, bins + slab.
    The first ``n_crowded`` particles all sit in cell (0, 0, 0), so a
    ``capacity`` below that count always leaves unslotted overflow
    particles."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    dims = jnp.asarray(grid_shape, jnp.float32)
    unit = jax.random.uniform(k1, (n, 3))
    pos = jnp.where(jnp.arange(n)[:, None] < n_crowded, unit, unit * dims)
    alive = jnp.arange(n) >= n_dead
    cells = cell_index(pos, grid_shape)
    n_cells = int(np.prod(grid_shape))
    if capacity is None:
        capacity = choose_capacity(
            int(np.max(np.bincount(np.asarray(cells)[np.asarray(alive)], minlength=n_cells)))
        )
    layout, overflow = build_bins(cells, alive, n_cells=n_cells, capacity=capacity)
    slab = build_bin_slab(pos, layout, grid_shape=grid_shape)
    fields = [jax.random.normal(k, grid_shape) for k in jax.random.split(k2, 6)]
    return dict(
        pos=pos, alive=alive, layout=layout, slab=slab, fields=fields,
        overflow=int(overflow), capacity=capacity,
    )


def _padded(fields, order):
    g = max_guard(order)
    return tuple(unfold_guards(f, g) for f in fields)


def test_eb_staggers_match_yee_grid():
    """core.EB_STAGGERS must stay the pic.grid Yee stagger order (core cannot
    import pic — this pin prevents silent drift)."""
    assert EB_STAGGERS == tuple(E_STAGGER) + tuple(B_STAGGER)


@pytest.mark.parametrize("order", [1, 2, 3])
@pytest.mark.parametrize("grid_shape", [GRID, (3, 7, 5)])
def test_fused_gather_matches_scatter_oracle(order, grid_shape):
    """All six components vs the per-particle scatter-gather oracle on a
    non-cubic grid with dead particles and empty bins."""
    wl = make_workload(300, grid_shape, n_dead=40)
    e_p, b_p = gather_fields_fused(
        wl["slab"], _padded(wl["fields"], order), wl["layout"],
        grid_shape=grid_shape, order=order,
    )
    got = jnp.concatenate([e_p, b_p], axis=-1)
    slotted = np.asarray(wl["layout"].particle_slot) >= 0
    assert slotted.sum() > 0 and (~slotted).sum() > 0
    for comp, stagger in enumerate(EB_STAGGERS):
        ref = gather_scatter(
            wl["pos"], _padded(wl["fields"], order)[comp], order=order, stagger=stagger
        )
        np.testing.assert_allclose(
            np.asarray(got[:, comp])[slotted], np.asarray(ref)[slotted],
            rtol=1e-5, atol=1e-5, err_msg=f"component {comp} (stagger {stagger})",
        )
    # dead/unslotted particles gather exactly 0 (they are in no bin)
    np.testing.assert_array_equal(np.asarray(got)[~slotted], 0.0)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_fused_gather_matches_six_call_path(order):
    """Fused == the six independent gather_matrix calls it replaces,
    including unslotted OVERFLOW particles (capacity too small)."""
    wl = make_workload(400, GRID, capacity=8, n_crowded=12)
    assert wl["overflow"] > 0, "workload must include unslotted overflow particles"
    e_p, b_p = gather_fields_fused(
        wl["slab"], _padded(wl["fields"], order), wl["layout"],
        grid_shape=GRID, order=order,
    )
    got = jnp.concatenate([e_p, b_p], axis=-1)
    for comp, stagger in enumerate(EB_STAGGERS):
        ref = gather_matrix(
            wl["pos"], _padded(wl["fields"], order)[comp], wl["layout"],
            grid_shape=GRID, order=order, stagger=stagger,
        )
        np.testing.assert_allclose(
            np.asarray(got[:, comp]), np.asarray(ref), rtol=1e-6, atol=1e-6,
            err_msg=f"component {comp}",
        )


@pytest.mark.parametrize("order", [1, 2])
def test_fused_gather_pallas_route_matches_xla(order):
    """gather_fields_fused with the Pallas megakernel (interpret off-TPU)
    == the pure-XLA reference, end to end through the slot scatter-back."""
    from repro.kernels.gather.ops import fused_bin_gather

    wl = make_workload(256, GRID, n_dead=16)
    want = gather_fields_fused(
        wl["slab"], _padded(wl["fields"], order), wl["layout"], grid_shape=GRID, order=order
    )
    got = gather_fields_fused(
        wl["slab"], _padded(wl["fields"], order), wl["layout"], grid_shape=GRID, order=order,
        fused_gather=fused_bin_gather,
    )
    for a, b, name in zip(got, want, ("E", "B")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5, err_msg=name)


def _uniform_sim(**cfg_kw):
    grid = GridSpec(shape=(6, 6, 6))
    parts = uniform_plasma(
        jax.random.PRNGKey(0), grid, ppc_each_dim=(2, 2, 2), density=1.0, u_thermal=0.1, jitter=1.0
    )
    cfg = PICConfig(grid=grid, dt=0.2, capacity=16, **cfg_kw)
    return Simulation(FieldState.zeros(grid.shape), parts, cfg)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_sim_level_fused_equals_unfused_six_call(order):
    """20 steps with gather='matrix' (fused, the default) pin the
    gather='matrix_unfused' six-call trajectory."""
    fused = _uniform_sim(order=order, deposition="matrix", gather="matrix")
    sixc = _uniform_sim(order=order, deposition="matrix", gather="matrix_unfused")
    fused.run(20)
    sixc.run(20)
    assert (fused.sorts, fused.rebuilds) == (sixc.sorts, sixc.rebuilds)
    for name in ("ex", "ey", "ez", "bx", "by", "bz"):
        np.testing.assert_allclose(
            np.asarray(getattr(fused.state.fields, name)),
            np.asarray(getattr(sixc.state.fields, name)),
            rtol=2e-5, atol=1e-6, err_msg=f"field {name} diverged",
        )
    np.testing.assert_allclose(
        np.asarray(fused.state.particles.pos), np.asarray(sixc.state.particles.pos),
        rtol=2e-5, atol=2e-5,
    )


# ---------------------------------------------------------------------------
# Structural guarantees: one slab staging per step, slab consistency.
# ---------------------------------------------------------------------------


def _slab_builds_per_traced_step(sim):
    before = binning.SLAB_BUILDS
    jax.make_jaxpr(partial(_pic_step, config=sim.config))(sim.state)
    return binning.SLAB_BUILDS - before


def test_one_slab_staging_per_fused_step():
    """The gather='matrix' + deposition='matrix' step stages the particle
    slab into bin order exactly ONCE (PR 1..4 paid >= 3 stagings: gather E,
    gather B, deposit); the carried slab serves the gather, the fresh one
    the deposition AND the next step's gather."""
    sim = _uniform_sim(order=2, deposition="matrix", gather="matrix")
    assert _slab_builds_per_traced_step(sim) == 1


def test_one_slab_staging_with_scatter_deposition():
    """gather='matrix' alone still stages exactly once per step."""
    sim = _uniform_sim(order=1, deposition="scatter", gather="matrix")
    assert _slab_builds_per_traced_step(sim) == 1


def test_unfused_ablation_keeps_per_call_staging():
    """The matrix_unfused ablation modes keep their historical per-call
    staging — no shared slab is built (or carried) for them."""
    sim = _uniform_sim(order=1, deposition="matrix_unfused", gather="matrix_unfused")
    assert _slab_builds_per_traced_step(sim) == 0
    assert sim.state.slab is None


def test_carried_slab_stays_consistent():
    """After any number of steps (including in-window sorts), the carried
    slab equals a fresh staging of (particles.pos, layout)."""
    sim = _uniform_sim(order=2, deposition="matrix", gather="matrix")
    sim.run(17, window=5)
    s = sim.state
    fresh = build_bin_slab(s.particles.pos, s.layout, grid_shape=sim.config.grid.shape)
    np.testing.assert_array_equal(np.asarray(s.slab.valid), np.asarray(fresh.valid))
    d_got = np.asarray(s.slab.d)[np.asarray(fresh.valid)]
    d_want = np.asarray(fresh.d)[np.asarray(fresh.valid)]
    np.testing.assert_array_equal(d_got, d_want)


# ---------------------------------------------------------------------------
# backend config resolution: the choice must reach the GATHER (use_pallas
# was silently dropped there before — kernels/gather/bin_gather was dead
# code — and the dispatcher backend must not regress that).
# ---------------------------------------------------------------------------


def _step_jaxpr(config):
    grid = config.grid
    parts = uniform_plasma(
        jax.random.PRNGKey(0), grid, ppc_each_dim=(2, 2, 2), density=1.0, u_thermal=0.05
    )
    sim = Simulation(FieldState.zeros(grid.shape), parts, config)
    return str(jax.make_jaxpr(partial(_pic_step, config=config))(sim.state))


@pytest.mark.parametrize("gather", ["matrix", "matrix_unfused"])
def test_backend_routes_into_gather(gather):
    """With scatter deposition, any pallas_call in the traced step belongs
    to the gather — PICConfig(backend="pallas") must put one there."""
    grid = GridSpec(shape=(6, 6, 6))
    base = dict(grid=grid, dt=0.2, order=1, deposition="scatter", gather=gather, capacity=16)
    assert "pallas_call" in _step_jaxpr(PICConfig(**base, backend="pallas"))
    assert "pallas_call" not in _step_jaxpr(PICConfig(**base, backend="xla"))


def test_spec_backend_reaches_gather_config():
    """DepositionSpec backend (including the deprecated use_pallas shim)
    resolves into PICConfig/DistConfig with the fused gather paired by
    default."""
    from repro.api import scenario
    from repro.api.facade import dist_config, pic_config
    from repro.api.spec import DepositionSpec

    with pytest.deprecated_call():
        spec = scenario("uniform", use_pallas=True)
    cfg = pic_config(spec)
    assert cfg.backend == "pallas" and cfg.gather == "matrix"

    spec = scenario("uniform", backend="pallas_reduced")
    assert pic_config(spec).backend == "pallas_reduced"

    with pytest.deprecated_call():
        dspec = scenario("uniform", grid=(8, 8, 8), mesh=(2, 2), use_pallas=True,
                         gather="matrix_unfused")
    dcfg = dist_config(dspec)
    assert dcfg.backend == "pallas" and dcfg.gather == "matrix_unfused"

    with pytest.raises(ValueError):
        DepositionSpec(gather="nope")
    with pytest.raises(ValueError):
        DepositionSpec(backend="nope")


def test_dist_config_rejects_scatter_gather():
    from repro.pic.distributed import DistConfig

    with pytest.raises(ValueError):
        DistConfig(local_grid=GridSpec(shape=(4, 4, 8)), dt=0.1, gather="scatter")


# ---------------------------------------------------------------------------
# packed-stagger weight sets (shape_functions.packed_axis_weights)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("order", [1, 2, 3])
def test_packed_axis_weights_zero_pad_true_support(order):
    """The unified-window weight sets equal the true-support sets embedded
    at their static offset, zero elsewhere — the property that lets all six
    components share one packed operand shape."""
    from repro.core import packed_axis_weights, shape_weights, support, unified_support

    d = jax.random.uniform(jax.random.PRNGKey(3), (64, 3))
    t, base = unified_support(order)
    w = packed_axis_weights(d, order)
    for axis in range(3):
        for staggered in (False, True):
            nt, b = support(order, staggered)
            want = np.zeros((64, t), np.float32)
            want[:, b - base : b - base + nt] = np.asarray(
                shape_weights(d[:, axis], order, staggered)
            )
            np.testing.assert_allclose(np.asarray(w[(axis, staggered)]), want, atol=1e-7)
