"""Gradient subsystem (repro.grad): custom-VJP permutation wrappers,
forward bit-identity of the differentiable window across remat policies,
AD-vs-central-FD validation in f64 (deposition orders 1-3 and the 20-step
LWFA acceptance run), the remat memory structure of the reverse pass, the
objective registry / GradSpec / trainable-params mapping, traced laser and
density overrides (no retrace across values), and the one-compile AdamW
fit with resumable checkpoints."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import GradSpec, scenario
from repro.api.facade import build_fields, build_particles, pic_config
from repro.core import policy_init
from repro.grad import (
    LEARNABLE,
    StateBuilder,
    default_params,
    fit_simulation,
    get_objective,
    make_objective,
    objective_names,
    permute_tree,
    permute_values,
    resolve_param,
    slot_gather,
)
from repro.pic.simulation import init_state, pic_run_window, run_window_diff


def _lwfa(**kw):
    kw.setdefault("grid", (6, 6, 24))
    kw.setdefault("ppc", 1)
    kw.setdefault("backend", "xla")
    return scenario("lwfa", **kw)


# ---------------------------------------------------------------------------
# custom-VJP permutation wrappers
# ---------------------------------------------------------------------------


def test_permute_values_forward_identity_and_vjp():
    """Forward is bitwise plain indexing; backward is the inverse scatter
    (equal to differentiating ``v[perm]`` directly), including under jit."""
    v = jax.random.normal(jax.random.PRNGKey(0), (17, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (17, 3))
    perm = jax.random.permutation(jax.random.PRNGKey(2), 17)

    np.testing.assert_array_equal(
        np.asarray(permute_values(v, perm)), np.asarray(v[perm])
    )
    g = jax.grad(lambda x: jnp.sum(permute_values(x, perm) * w))(v)
    gref = np.zeros_like(np.asarray(v))
    gref[np.asarray(perm)] = np.asarray(w)
    np.testing.assert_allclose(np.asarray(g), gref, rtol=1e-6)
    # same cotangent the native indexing rule produces
    gnat = jax.grad(lambda x: jnp.sum(x[perm] * w))(v)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(gnat))
    gjit = jax.jit(jax.grad(lambda x: jnp.sum(permute_values(x, perm) * w)))(v)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(gjit))


def test_permute_tree_mixed_dtypes():
    """Float leaves go through the custom VJP, int/bool leaves through plain
    indexing (no float0 cotangent plumbing) — all bitwise-permuted, and
    grads flow through the float leaves."""
    perm = jax.random.permutation(jax.random.PRNGKey(0), 9)
    tree = {
        "f": jax.random.normal(jax.random.PRNGKey(1), (9, 2)),
        "i": jnp.arange(9, dtype=jnp.int32),
        "b": jnp.arange(9) % 2 == 0,
    }
    out = permute_tree(tree, perm)
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(out[k]), np.asarray(tree[k][perm])
        )
    g = jax.grad(lambda f: jnp.sum(permute_tree({**tree, "f": f}, perm)["f"] ** 2))(
        tree["f"]
    )
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(tree["f"]), rtol=1e-6)


def test_slot_gather_masks_invalid_slots_in_vjp():
    """Forward clamps -1 pads to particle 0 (the layout's padding trick,
    bitwise-identical to the raw gather); the VJP must NOT leak those pads'
    cotangents onto particle 0."""
    vals = jax.random.normal(jax.random.PRNGKey(0), (10, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 3))
    slots = jnp.array([[0, 3, -1], [9, -1, -1]])

    out = slot_gather(vals, slots)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(vals[jnp.maximum(slots, 0)])
    )

    g = jax.grad(lambda v: jnp.sum(slot_gather(v, slots) * w))(vals)
    gref = np.zeros_like(np.asarray(vals))
    wn, sn = np.asarray(w), np.asarray(slots)
    for i in range(sn.shape[0]):
        for j in range(sn.shape[1]):
            if sn[i, j] >= 0:
                gref[sn[i, j]] += wn[i, j]
    np.testing.assert_allclose(np.asarray(g), gref, rtol=1e-6)
    # the naive (unmasked) rule WOULD differ: pads alias particle 0
    gnaive = jax.grad(lambda v: jnp.sum(v[jnp.maximum(slots, 0)] * w))(vals)
    assert not np.allclose(np.asarray(gnaive), gref)


# ---------------------------------------------------------------------------
# the differentiable window
# ---------------------------------------------------------------------------


def _window_problem(n_steps):
    spec = _lwfa(steps=n_steps, window=n_steps)
    config = dataclasses.replace(pic_config(spec), backend="xla")
    state, overflow = init_state(build_fields(spec), build_particles(spec), config)
    assert not overflow
    return spec, config, state


@pytest.mark.parametrize("remat", ["none", "step", "chunk"])
def test_run_window_diff_forward_bit_identity(remat):
    """Acceptance: the diff window's forward pass is BIT-identical to the
    production window — every int and float leaf of the state and the
    bundle — for every remat policy (jax.checkpoint's primal is identity)."""
    spec, config, state = _window_problem(8)
    ref = pic_run_window(
        state, policy_init(), config, 8, policy=spec.sort.policy,
        with_energies=False, donate=False,
    )
    got = run_window_diff(
        state, policy_init(), config, 8, policy=spec.sort.policy,
        remat=remat, remat_chunk=4 if remat == "chunk" else 0,
    )
    rleaves, rdef = jax.tree.flatten(ref)
    gleaves, gdef = jax.tree.flatten(got)
    assert rdef == gdef
    for r, g in zip(rleaves, gleaves):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_run_window_diff_rejects_pallas_backends():
    spec, config, state = _window_problem(4)
    bad = dataclasses.replace(config, backend="auto")
    with pytest.raises(ValueError, match="xla"):
        run_window_diff(state, policy_init(), bad, 4)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_grad_matches_central_fd_per_order(order):
    """AD through a short LWFA window matches central finite differences in
    f64 at every deposition order the matrix formulation supports."""
    with jax.enable_x64(True):
        spec = _lwfa(order=order)
        loss_fn, params = make_objective(
            spec, learn=("laser.a0", "density"), steps=4,
            objective_kwargs={"e_min": 0.1}, dtype=jnp.float64,
        )
        value = lambda p: float(loss_fn(p)[0])
        grads = jax.grad(lambda p: loss_fn(p)[0])(params)
        for name, v in params.items():
            eps = 1e-4 * max(1.0, abs(float(v)))
            up = value({**params, name: v + eps})
            dn = value({**params, name: v - eps})
            fd = (up - dn) / (2 * eps)
            np.testing.assert_allclose(
                float(grads[name]), fd, rtol=1e-3,
                err_msg=f"order={order} param={name}",
            )


def test_grad_matches_central_fd_20_step_lwfa():
    """Acceptance: jax.grad through a >=20-step windowed LWFA run matches
    central FD on EVERY learned parameter (f64, rtol <= 1e-3)."""
    with jax.enable_x64(True):
        spec = _lwfa()
        learn = tuple(sorted(LEARNABLE))
        loss_fn, params = make_objective(
            spec, learn=learn, steps=20,
            objective_kwargs={"e_min": 0.1}, dtype=jnp.float64,
        )
        value = lambda p: float(loss_fn(p)[0])
        grads = jax.grad(lambda p: loss_fn(p)[0])(params)
        assert set(grads) == set(learn)
        for name, v in params.items():
            eps = 1e-4 * max(1.0, abs(float(v)))
            up = value({**params, name: v + eps})
            dn = value({**params, name: v - eps})
            fd = (up - dn) / (2 * eps)
            assert np.isfinite(fd) and fd != 0.0, f"degenerate FD for {name}"
            np.testing.assert_allclose(
                float(grads[name]), fd, rtol=1e-3, err_msg=f"param={name}"
            )


def _stacked_scan_outputs(jaxpr, n):
    """Count scan outputs whose leading dim is the step count — the stacked
    per-step residuals reverse-mode stores. Recurses into sub-jaxprs."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            total += sum(
                1 for v in eqn.outvars
                if getattr(v.aval, "shape", ()) and v.aval.shape[0] == n
            )
        for p in eqn.params.values():
            items = p if isinstance(p, (tuple, list)) else (p,)
            for item in items:
                if hasattr(item, "jaxpr"):  # ClosedJaxpr
                    total += _stacked_scan_outputs(item.jaxpr, n)
                elif hasattr(item, "eqns"):  # raw Jaxpr
                    total += _stacked_scan_outputs(item, n)
    return total


def test_remat_bounds_reverse_pass_residuals():
    """Acceptance (structural): under remat="step" the grad program's
    per-step stacked residuals are a small CARRY-sized set, independent of
    the window length; remat="none" stores residuals per step."""
    counts = {}
    for remat, n in [("step", 4), ("step", 8), ("none", 8)]:
        loss_fn, params = make_objective(
            _lwfa(), learn=("laser.a0",), steps=n, remat=remat,
            objective_kwargs={"e_min": 0.1},
        )
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: loss_fn(p)[0]))(params)
        counts[(remat, n)] = _stacked_scan_outputs(jaxpr.jaxpr, n)
    assert counts[("step", 4)] == counts[("step", 8)]  # window-length bound
    assert counts[("step", 8)] * 2 < counts[("none", 8)]


# ---------------------------------------------------------------------------
# params / objectives / GradSpec
# ---------------------------------------------------------------------------


def test_param_mapping_and_aliases():
    assert resolve_param("laser.w0") == "laser.waist"
    assert resolve_param("laser.tau") == "laser.duration"
    with pytest.raises(KeyError, match="unknown trainable"):
        resolve_param("laser.phase")
    spec = _lwfa()
    p = default_params(spec, ("laser.a0", "density"))
    assert float(p["laser.a0"]) == spec.laser.a0
    assert float(p["density"]) == spec.plasma.density
    with pytest.raises(ValueError, match="laser"):
        default_params(scenario("uniform", backend="xla"), ("laser.a0",))


def test_objective_registry():
    names = objective_names()
    for name in ("injected_charge", "mean_beam_energy", "field_energy_band"):
        assert name in names
    assert get_objective("injected_charge").maximize
    with pytest.raises(KeyError, match="unknown objective"):
        get_objective("nope")


def test_gradspec_validation_and_roundtrip():
    gs = GradSpec(learn=("laser.w0", "density"), remat="chunk", remat_chunk=4,
                  objective_kwargs={"e_min": 0.2})
    assert gs.learn == ("laser.waist", "density")  # canonicalized
    assert gs.okwargs == {"e_min": 0.2}
    assert GradSpec.from_dict(gs.to_dict()) == gs
    with pytest.raises(ValueError):
        GradSpec(remat="everything")
    with pytest.raises((ValueError, KeyError)):
        GradSpec(learn=())


def test_traced_overrides_build_without_retrace():
    """Satellite regression: laser amplitude/waist/duration and density are
    traced jnp scalars through the state build — changing their VALUES
    reuses one compiled build, and the fields actually respond (both Ex and
    By scale linearly with a0)."""
    spec = _lwfa()
    config = dataclasses.replace(pic_config(spec), backend="xla")
    builder = StateBuilder(spec, config)
    traces = []

    def build(p):
        traces.append(1)
        return builder.build(p)

    jbuild = jax.jit(build)
    s1 = jbuild({"laser.a0": jnp.float32(2.0), "density": jnp.float32(spec.plasma.density)})
    s2 = jbuild({"laser.a0": jnp.float32(2.5), "density": jnp.float32(2 * spec.plasma.density)})
    assert len(traces) == 1  # values changed, program did not
    np.testing.assert_allclose(
        np.asarray(s2.fields.ex), np.asarray(s1.fields.ex) * 1.25, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(s2.fields.by), np.asarray(s1.fields.by) * 1.25, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(s2.particles.w), np.asarray(s1.particles.w) * 2.0, rtol=1e-5
    )
    # index machinery is shared and untouched by the traced part
    np.testing.assert_array_equal(
        np.asarray(s1.layout.slots), np.asarray(s2.layout.slots)
    )


# ---------------------------------------------------------------------------
# the fit loop
# ---------------------------------------------------------------------------


def test_fit_improves_objective_without_recompiling():
    """Acceptance: 3 AdamW iterations on the tiny LWFA improve the injected
    charge, every gradient is finite, and the window traced EXACTLY once —
    optimizer steps change array values, never the compiled program."""
    result = fit_simulation(
        _lwfa(), learn=("laser.a0",), steps=6, iters=3,
        objective_kwargs={"e_min": 0.1},
    )
    assert result.compiles == 1
    traj = result.objective_trajectory
    assert traj[-1] > traj[0]
    for r in result.history:
        assert np.isfinite(r["loss"]) and np.isfinite(r["grad_norm"])
        assert all(np.isfinite(g) for g in r["grads"].values())
    assert result.params["laser.a0"] != result.history[0]["params"]["laser.a0"]
    assert result.grad.objective == "injected_charge"


def test_fit_checkpoint_resume(tmp_path):
    """A crashed fit resumes from its latest {params, optimizer} checkpoint:
    the second call skips the completed iterations and continues the same
    trajectory."""
    kw = dict(learn=("laser.a0",), steps=4, iters=2,
              objective_kwargs={"e_min": 0.1},
              checkpoint_dir=str(tmp_path / "fit"))
    first = fit_simulation(_lwfa(), **kw)
    assert [r["iter"] for r in first.history] == [0, 1]
    resumed = fit_simulation(_lwfa(), **{**kw, "iters": 4})
    assert [r["iter"] for r in resumed.history] == [2, 3]
    np.testing.assert_allclose(
        resumed.history[0]["params"]["laser.a0"],
        first.params["laser.a0"], rtol=1e-6,
    )
