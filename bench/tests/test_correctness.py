"""The comparison that decides ``correct``, driven through the benchmark's
own run path on the CPU at a small size: a sound program passes, and each
fault planted under the timed path, and the lower-precision control, fail.

The run path is `run.run_cell` with the chip look skipped; the limits are
the committed ones of each cell. The faults are those a single-chip PIC
cell can have: a step that returns its state unchanged, half of the
particles left out of the deposition (the other half counted twice), and
one particle's momentum altered where the push produces it. The control
is the program with its contractions at ``Precision.HIGH``; XLA's CPU
backend ignores precision, so here the three bf16 passes of HIGH are
computed explicitly in place of each contraction.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import control
import reference
import run

SMALL = {
    "uniform.o3": {"scenario": "uniform", "spec": {"grid": [8, 8, 8], "ppc": 2, "u_thermal": 0.02,
                                                  "backend": "xla"}},
    "lwfa.o2": {"scenario": "lwfa", "spec": {"grid": [8, 8, 32], "ppc": 2, "capacity": 48, "dt": 0.35,
                                            "backend": "xla", "laser": {"z_center": 9.6}}},
    "uniform.o1": {"scenario": "uniform", "spec": {"grid": [8, 8, 8], "ppc": 2, "u_thermal": 0.02,
                                                  "backend": "xla"}},
}
CELL = "uniform.o3"


def drive(cell: str = CELL, seed: int = 2**40 + 7) -> dict:
    bench = run.load_benchmark()
    spec = run.find_cell(bench, cell)
    traffic = dict(run.load_named("traffic", spec["traffic"]), window=4)
    jax.clear_caches()
    try:
        return run.run_cell(cell, SMALL[cell], traffic, seed=seed, seconds=0.0, trace=False,
                            devices=jax.devices()[:1], limits=run.load_named("limits", cell),
                            per_layer=[], log=lambda m: None)
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(cell):
    line = drive(cell)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"


def test_stale_step_fails(monkeypatch):
    from repro.pic import simulation

    real = simulation._pic_step

    def stale(state, config):
        new, stats = real(state, config)
        return dataclasses.replace(state, step=new.step), stats

    monkeypatch.setattr(simulation, "_pic_step", stale)
    line = drive()
    assert not line["correct"]
    assert line["checks"]["field_gap"]["value"] > line["checks"]["field_gap"]["limit"]


def test_half_the_particles_deposited_fails(monkeypatch):
    from repro.core.binning import bin_slab_staging
    from repro.pic import simulation

    real = simulation._deposit_current

    def half(pos, v, qw, layout, slab, cells, config, values=None):
        keep = jnp.arange(qw.shape[0]) < qw.shape[0] // 2
        qw = jnp.where(keep, 2.0 * qw, 0.0)
        slab, values = bin_slab_staging(pos, v, qw, layout, grid_shape=config.grid.shape)
        return real(pos, v, qw, layout, slab, cells, config, values=values)

    monkeypatch.setattr(simulation, "_deposit_current", half)
    line = drive()
    assert not line["correct"]
    assert line["checks"]["field_gap"]["value"] > line["checks"]["field_gap"]["limit"]


@pytest.mark.parametrize("cell", ["uniform.o3", "uniform.o1"])
def test_one_altered_momentum_fails(cell):
    undo = control.alter_momentum()
    try:
        line = drive(cell)
    finally:
        undo()
    assert not line["correct"]
    # uniform.o3 compares no momentum_gap: its field_gap catches the fault
    number = "momentum_gap" if "momentum_gap" in line["checks"] else "field_gap"
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]


def test_lower_precision_control_fails(monkeypatch):
    real = jnp.einsum

    def einsum(subscripts, a, b, *, precision=None, **kw):
        if precision != jax.lax.Precision.HIGH:
            return real(subscripts, a, b, precision=precision, **kw)

        def split(x):
            hi = x.astype(jnp.bfloat16).astype(jnp.float32)
            return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)

        (ah, al), (bh, bl) = split(a), split(b)
        dot = lambda x, y: real(subscripts, x, y, precision=jax.lax.Precision.HIGHEST, **kw)
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)

    monkeypatch.setattr(jnp, "einsum", einsum)
    before = control.set_contraction_precision(jax.lax.Precision.HIGH)
    try:
        line = drive()
    finally:
        control.restore(before)
    assert not line["correct"], line["checks"]


def test_a_checked_window_without_a_global_sort_is_refused(monkeypatch):
    monkeypatch.setattr(run, "due_sort", lambda sim: None)
    with pytest.raises(RuntimeError, match="no global sort"):
        drive()


def test_sound_run_prints_the_position_gap():
    line = drive()
    assert 0.0 <= line["info"]["position_gap"] < 1e-4
    assert list(line)[-2:] == ["info", "checks"]


def test_position_gap_survives_a_wrap_on_one_side():
    rng = np.random.default_rng(3)
    ref = rng.uniform(0.0, 8.0, size=(1000, 3))
    ref[0, 0] = 7.9999999
    got = ref + rng.normal(0.0, 1e-7, size=ref.shape)
    got[0, 0] = 1e-7
    assert check.position_gap(got[::-1], ref, (8, 8, 8)) < 1e-6
    got[5, 1] += 0.01
    assert check.position_gap(got, ref, (8, 8, 8)) > 1e-3


@pytest.mark.parametrize("order", [1, 2, 3])
def test_reference_agrees_with_the_program(order):
    """At a small size the reference and the program's own step agree to
    float32 rounding over a few steps."""
    from repro.api import make_simulation, scenario

    sim = make_simulation(scenario("uniform", grid=(8, 8, 8), ppc=2, order=order, window=4, backend="xla"))
    sim.run(4)
    start = run.snapshot(sim)
    sim.run(3)
    end = run.snapshot(sim)
    c = sim.config
    ref = reference.run(start, 3, order=order, dt=c.dt, charge=c.charge, mass=c.mass, dx=c.grid.dx)
    scale = max(np.abs(f).max() for f in ref["fields"])
    assert max(np.abs(a - b).max() for a, b in zip(end["fields"], ref["fields"])) < 1e-5 * scale
    assert np.abs(np.sort(end["u"], axis=0) - np.sort(ref["u"], axis=0)).max() < 1e-6


def test_reference_shapes_partition_unity():
    """Each particle's weights over the nodes around its cell sum to one,
    for every order and stagger: no node of the support is missed."""
    pos = jnp.stack([jnp.linspace(0.0, 7.999, 101)] * 3, axis=1)
    for order in (1, 2, 3):
        for stagger in reference.E_STAGGER + reference.B_STAGGER + ((0, 0, 0),):
            _, w = reference._cell_weights(pos, order, stagger)
            np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 1.0, rtol=1e-6)
