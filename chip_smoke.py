#!/usr/bin/env python3
"""Run the PIC main path on a TPU and check what comes out.

    python chip_smoke.py             # one chip: uniform 64^3 and LWFA runs
    python chip_smoke.py --chips 4   # the 2x2 mesh driver vs one chip

Everything runs through ``make_simulation(scenario(...)).run()`` with the
default ``backend="auto"`` and the health sentinel on. One chip runs
``uniform`` at 64^3 x 8 ppc (2,097,152 particles), order 3, and ``lwfa``
at 32 x 32 x 256, order 2: each checks deposition and gather on its own
initial particles against the oracle, then runs 3 windows of 8 steps
with finite energies, exact charge, and no retry, restart or backend
demotion.

``--chips 4`` runs only the distributed path: uniform 64^3 x 8 ppc, order
2, on a 2x2 mesh for 2 windows, against the single-chip run of the same
spec (integers exact, floats rtol 2e-5) and with overlapped halos against
serialized ones (bit-identical), shards on four distinct devices.

The float64 oracle runs on the host CPU device of the same process. Times
printed are informational. The last line of standard output is
``{"ok": true, "device": {...}}``; any failed phase exits non-zero before
it. Without a TPU the script exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import math
import os
import sys
import tempfile
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.api import make_simulation, scenario  # noqa: E402
from repro.core import (  # noqa: E402
    SortPolicyConfig,
    cell_index,
    deposit_current,
    gather_scatter,
    max_guard,
    unfold_guards,
)
from repro.core.binning import bin_slab_staging, choose_capacity  # noqa: E402
from repro.core.health import HealthConfig  # noqa: E402
from repro.core.shape_functions import CONTRACTION_PRECISION  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.pic.grid import B_STAGGER, E_STAGGER, FieldState  # noqa: E402
from repro.pic.pusher import lorentz_gamma  # noqa: E402
from repro.pic.simulation import _deposit_current, _gather_fields  # noqa: E402

#: deposition and gather vs the float64 oracle: the CPU tests' bound
REL_BOUND = 1e-5
#: particles per oracle call (bounds the host memory of the float64 scatter)
ORACLE_CHUNK = 1 << 18
WINDOW = 8
#: a hung device call dumps every thread's stack and exits non-zero inside
#: the 1200 s a smoke run is given, instead of running into the limit
WATCHDOG_S = 1100


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(n_chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform is {devices[0].platform!r})", file=sys.stderr)
        sys.exit(2)
    if len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} TPU chips, JAX sees {len(devices)}", file=sys.stderr)
        sys.exit(2)
    return devices


# ---------------------------------------------------------------------------
# chip side: the step's own deposition and gather on the initial state
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("config",))
def _chip_current(state, config):
    p = state.particles
    v = p.u / lorentz_gamma(p.u)[:, None]
    qw = config.charge * p.w * p.alive.astype(p.pos.dtype)
    slab, values = bin_slab_staging(p.pos, v, qw, state.layout, grid_shape=config.grid.shape)
    cells = cell_index(p.pos, config.grid.shape)
    j = _deposit_current(p.pos, v, qw, state.layout, slab, cells, config, values=values)
    return v, qw, jnp.stack(j)


@partial(jax.jit, static_argnames=("config",))
def _chip_gather(state, fields, config):
    e, b = _gather_fields(state.particles.pos, fields, state.layout, state.slab, config)
    return jnp.concatenate([e, b], axis=-1)


def random_fields(grid_shape, seed: int = 7):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    return FieldState(*(jax.random.normal(k, grid_shape) for k in keys))


# ---------------------------------------------------------------------------
# float64 oracle on the host CPU device
# ---------------------------------------------------------------------------


def _chunks(n: int):
    size = min(n, ORACLE_CHUNK)
    return size, range(0, n, size)


def _padded_chunk(a, start: int, size: int):
    part = a[start:start + size]
    if part.shape[0] < size:  # zero-weight padding keeps one compiled shape
        part = np.concatenate([part, np.zeros((size - part.shape[0],) + part.shape[1:], a.dtype)])
    return part


def oracle_current(pos, v, qw, *, grid_shape, order: int, inv_vol: float):
    """[Jx, Jy, Jz] of the float64 `deposit_scatter` oracle, folded."""
    pos, v, qw = (np.asarray(a, np.float64) for a in (pos, v, qw))
    size, starts = _chunks(pos.shape[0])
    total = 0.0
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        for s in starts:
            j = deposit_current(
                jnp.asarray(_padded_chunk(pos, s, size)), jnp.asarray(_padded_chunk(v, s, size)),
                jnp.asarray(_padded_chunk(qw, s, size)), grid_shape=grid_shape, order=order,
                method="scatter",
            )
            total = total + np.stack([np.asarray(c) for c in j])
    return total * inv_vol


def oracle_gather(pos, fields, *, order: int):
    """(N, 6) Ex..Bz of the float64 `gather_scatter` oracle."""
    pos = np.asarray(pos, np.float64)
    comps = [np.asarray(f, np.float64) for f in (*fields.e(), *fields.b())]
    size, starts = _chunks(pos.shape[0])
    out = []
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        padded = [unfold_guards(jnp.asarray(f), max_guard(order)) for f in comps]
        for s in starts:
            p = jnp.asarray(_padded_chunk(pos, s, size))
            out.append(np.stack([
                np.asarray(gather_scatter(p, f, order=order, stagger=st))
                for f, st in zip(padded, (*E_STAGGER, *B_STAGGER))
            ], axis=-1))
    return np.concatenate(out)[: pos.shape[0]]


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = float(np.abs(want).max())
    return float(np.abs(got - want).max()) / scale if scale > 0 else float(np.abs(got).max())


def oracle_errors(state, config) -> tuple[float, float]:
    """(deposition, gather) relative error of the chip's bin route against
    the float64 oracle, worst component, on ``state``'s particles."""
    v, qw, j = _chip_current(state, config)
    p = state.particles
    want_j = oracle_current(
        p.pos, v, qw, grid_shape=config.grid.shape, order=config.order,
        inv_vol=1.0 / config.grid.cell_volume,
    )
    dep = max(rel_err(j[k], want_j[k]) for k in range(3))

    fields = random_fields(config.grid.shape)
    got = np.asarray(_chip_gather(state, fields, config))
    want = oracle_gather(p.pos, fields, order=config.order)
    alive = np.asarray(p.alive)
    gat = max(rel_err(got[alive, k], want[alive, k]) for k in range(6))
    return dep, gat


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def dispatch_report(sim) -> dict:
    """{op: {"backend", "offered", "timings_us"}} for the driver's dispatch
    keys: what each resolved to (a memo hit), what the chip was offered,
    and the autotune medians (None where one candidate was offered)."""
    cfg = sim.config
    key = dict(order=cfg.order, grid_shape=cfg.grid.shape, capacity=cfg.capacity)
    ops = dispatch.ops_for_modes(cfg.deposition, cfg.gather)
    resolved = dispatch.prewarm(ops, requested=cfg.backend, **key)
    return {op: {"backend": resolved[op], **info}
            for op, info in dispatch.describe(ops, **key).items()}


def run_single(name: str, **overrides) -> None:
    """One scenario through the facade: oracle check, then 3 windows."""
    spec = scenario(name, steps=3 * WINDOW, window=WINDOW, health=HealthConfig(enable=True),
                    **overrides)
    t0 = time.perf_counter()
    sim = make_simulation(spec)
    cfg = sim.config
    n_particles = int(sim.state.particles.pos.shape[0])
    print(f"{name}: grid={cfg.grid.shape} particles={n_particles} order={cfg.order} "
          f"capacity={cfg.capacity} backend={cfg.backend} setup {time.perf_counter() - t0:.1f}s")
    print(f"{name}: dispatch {dispatch_report(sim)}")

    dep, gat = oracle_errors(sim.state, cfg)
    print(f"{name}: vs float64 oracle: deposition rel err {dep:.3e}, gather rel err "
          f"{gat:.3e} (bound {REL_BOUND}, precision {CONTRACTION_PRECISION.name})")
    check(dep < REL_BOUND and gat < REL_BOUND, f"{name} misses the oracle bound")

    def charge() -> float:
        p = sim.state.particles
        w = np.asarray(p.w, np.float64)[np.asarray(p.alive)]
        return cfg.charge * math.fsum(w)

    q0, n0 = charge(), sim.diagnostics()["n_alive"]
    times = []
    for k in range(3):
        t0 = time.perf_counter()
        sim.run(WINDOW)
        times.append(time.perf_counter() - t0)
        d = sim.diagnostics()
        q = charge()
        print(f"{name}: window {k + 1}: step {d['step']} n_alive {d['n_alive']} charge {q!r} "
              f"field {d['field_energy']!r} kinetic {d['kinetic_energy']!r} "
              f"total {d['total_energy']!r} ({times[-1]:.2f}s)")
        check(all(math.isfinite(d[e]) for e in ("field_energy", "kinetic_energy", "total_energy")),
              f"{name}: non-finite energy in window {k + 1}")
        check(q == q0 and d["n_alive"] == n0, f"{name}: charge {q!r} != {q0!r} or particles lost")
    steady = (times[1] + times[2]) / (2 * WINDOW)
    print(f"{name}: halts {sim.halts} growths {sim.growths} retries {sim.retries} "
          f"restarts {sim.restarts} backend {sim.config.backend}")
    print(f"{name}: first window {times[0]:.2f}s (compile + {WINDOW} steps), "
          f"steady {steady * 1e3:.2f} ms/step (informational)")
    check(sim.retries == 0 and sim.restarts == 0, f"{name}: supervisor retried or restarted")
    check(sim.config.backend == cfg.backend, f"{name}: backend demoted to {sim.config.backend}")
    bad = {h: c for h, c in sim.halts.items() if h not in ("bin_overflow",)}
    check(not bad, f"{name}: unexpected halts {bad}")


def run_mesh(devices, grid=(64, 64, 64)) -> None:
    """2x2 mesh driver vs the single-chip driver, and overlapped vs
    serialized halos."""
    # the host wall-clock sort trigger differs between drivers; the
    # interval keeps both on the same sort cadence
    policy = SortPolicyConfig(sort_interval=20, sort_trigger_perf_enable=False)
    base = dict(grid=grid, ppc=2, order=2, steps=2 * WINDOW, window=WINDOW,
                diagnostics_every=1, policy=policy, health=HealthConfig(enable=True))
    runs = {}
    for label, extra in (("single", {}), ("mesh", {"mesh": "2x2"}),
                         ("mesh_overlap", {"mesh": "2x2", "overlap_halo": True})):
        t0 = time.perf_counter()
        sim = make_simulation(scenario("uniform", **base, **extra))
        sim.run()
        runs[label] = sim
        d = sim.diagnostics()
        print(f"{label}: {d} retries {sim.retries} restarts {sim.restarts} "
              f"halts {sim.halts} ({time.perf_counter() - t0:.1f}s)")
        check(sim.retries == 0 and sim.restarts == 0, f"{label}: supervisor retried")

    single, mesh, over = runs["single"], runs["mesh"], runs["mesh_overlap"]
    used = {sh.device for a in (*mesh.fields, mesh.pos) for sh in a.addressable_shards}
    print(f"mesh shards on devices {sorted(d.id for d in used)} of "
          f"{[d.id for d in devices[:4]]}")
    check(len(used) == 4, f"mesh state sits on {len(used)} device(s), not 4")

    # parity with one chip: integers exact, floats at rtol 2e-5
    ds, dm = single.diagnostics(), mesh.diagnostics()
    check(ds["n_alive"] == dm["n_alive"], f"n_alive {ds['n_alive']} != {dm['n_alive']}")
    q1 = math.fsum(np.asarray(single.state.particles.w, np.float64)[np.asarray(
        single.state.particles.alive)])
    qm = math.fsum(np.asarray(mesh.w, np.float64)[np.asarray(mesh.alive)])
    check(q1 == qm, f"charge {q1!r} != {qm!r}")
    names = ("ex", "ey", "ez", "bx", "by", "bz")
    worst = 0.0
    for name, fm in zip(names, mesh.fields):
        f1 = np.asarray(getattr(single.state.fields, name))
        fm = np.asarray(fm)
        worst = max(worst, rel_err(fm, f1))
        np.testing.assert_allclose(fm, f1, rtol=2e-5, atol=1e-6, err_msg=name)
    for key in ("field_energy", "kinetic_energy", "total_energy"):
        check(abs(ds[key] - dm[key]) <= 2e-5 * abs(ds[key]), f"{key}: {ds[key]!r} vs {dm[key]!r}")
    for hs, hm in zip(single.history, mesh.history, strict=True):
        check(hs["step"] == hm["step"] and hs["n_alive"] == hm["n_alive"], f"history {hs} {hm}")
        check(abs(hs["total_energy"] - hm["total_energy"]) <= 2e-5 * abs(hs["total_energy"]),
              f"history energy {hs} vs {hm}")
    print(f"mesh vs single chip: n_alive {dm['n_alive']} equal, charge {qm!r} equal, "
          f"worst field rel err {worst:.3e}, energies within 2e-5")

    # overlapped halo exchange: bit-identical to serialized
    for name, fa, fb in zip(names, mesh.fields, over.fields):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb), err_msg=name)
    for attr in ("pos", "u", "w", "alive"):
        np.testing.assert_array_equal(np.asarray(getattr(mesh, attr)),
                                      np.asarray(getattr(over, attr)), err_msg=attr)
    check(mesh.diagnostics() == over.diagnostics(), "overlap diagnostics differ")
    print("mesh overlap_halo=True vs serialized halos: bit-identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 mesh path and its comparisons")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)

    devices = require_tpu(args.chips)

    cache_dir = enable_compile_cache()
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; compile cache {cache_dir}")
    with tempfile.TemporaryDirectory() as tmp:
        # a fresh autotune cache: nothing measured elsewhere is read back
        os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(tmp, "autotune.json")
        try:
            if args.chips == 4:
                run_mesh(devices)
            else:
                # capacity from the lattice's 8 particles per cell
                run_single("uniform", grid=(64, 64, 64), ppc=2, order=3,
                           capacity=choose_capacity(8))
                run_single("lwfa", grid=(32, 32, 256), order=2)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
