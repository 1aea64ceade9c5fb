"""The PIC program's own measurement (docs/sim_loop.md, "Profiling a run"):
device scopes in the compiled window, host spans on the profiler's clock,
and the sorter counters the drivers total from each window bundle."""

import json
import os
import re
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.pic.simulation as simulation
from repro.core import REASON_NAMES, SortPolicyConfig, policy_init
from repro.distributed.fault import no_fault_vec
from repro.pic import FieldState, GridSpec, PICConfig, Simulation, uniform_plasma

ROOT = Path(__file__).resolve().parent.parent

#: every phase scope of the single-device window
PHASES = (
    "pic.gather", "pic.push", "pic.gpma", "pic.gpma.delete", "pic.gpma.rank",
    "pic.gpma.gaps", "pic.gpma.insert", "pic.stage", "pic.deposit", "pic.maxwell",
    "pic.policy", "pic.global_sort", "pic.diag", "pic.mask",
)
#: the host spans of a window that neither halts nor checkpoints
SPANS = ("pic.window", "pic.window.launch", "pic.window.fetch", "pic.window.consume")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# a fixed-interval sort every 4 steps, so two 8-step windows hold sorts
POLICY = SortPolicyConfig(sort_interval=4, min_sort_interval=1, sort_trigger_perf_enable=False)


def _sim(order=1, shape=(6, 6, 6)):
    grid = GridSpec(shape=shape)
    parts = uniform_plasma(
        jax.random.PRNGKey(0), grid, ppc_each_dim=(2, 2, 2), density=1.0, u_thermal=0.05
    )
    cfg = PICConfig(grid=grid, dt=0.2, order=order, capacity=16)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return Simulation(FieldState.zeros(grid.shape), parts, cfg, policy=POLICY)


def _scopes(op_name: str) -> list[str]:
    return [c for c in op_name.split("/") if c.startswith("pic.")]


@pytest.fixture(scope="module")
def v5e():
    """One described TPU v5e chip to compile for (no chip needed), or a skip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(
    scope="module", params=[("cpu", 1), ("cpu", 3), ("v5e", 1)],
    ids=["cpu-order1", "cpu-order3", "v5e-order1"],
)
def window_hlo(request):
    """The compiled window program of a tiny uniform grid, as HLO text: for
    the host CPU, and for a described TPU v5e, whose compiler treats the
    ops' metadata its own way."""
    target, order = request.param
    sim = _sim(order=order)
    args = (sim.state, policy_init(), jnp.asarray(4, jnp.int32), no_fault_vec())
    if target == "v5e":
        dev = request.getfixturevalue("v5e")
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), args)
    lowered = simulation._pic_run_window_donated.lower(
        *args, sim.config, POLICY, 4, True, None, False,
    )
    return lowered.compile().as_text()


def test_every_phase_scope_appears(window_hlo):
    innermost = {_scopes(n)[-1] for n in OP_NAME.findall(window_hlo) if _scopes(n)}
    assert set(PHASES) <= innermost, sorted(set(PHASES) - innermost)


def test_rank_scan_has_no_loop_and_carries_its_phase(window_hlo):
    """The within-bin rank runs in the GPMA rank every step and in the
    global sort's bin build: a run-start max-scan (`rank_in_runs`), with no
    search loop left, whose ops carry their caller's phase."""
    names = OP_NAME.findall(window_hlo)
    loops = [n for n in names if re.search(r"jit\((gpma_update|build_bins)\)/.*while", n)]
    assert not loops, set(loops)
    scan = [n for n in names if n.endswith("/max")]
    rank = [n for n in scan if "jit(gpma_update)" in n]
    bins = [n for n in scan if "jit(build_bins)" in n]
    assert rank, "no max-scan in the GPMA update"
    assert bins, "no max-scan in the global sort's bin build"
    assert all(_scopes(n)[-1] == "pic.gpma.rank" for n in rank), set(rank)
    assert all(_scopes(n)[-1] == "pic.global_sort" for n in bins), set(bins)


def test_no_phase_scope_nests_in_another(window_hlo):
    """Scopes nest only in themselves or as their own sub-scopes
    (``pic.gpma`` > ``pic.gpma.rank``), so the innermost names one phase."""
    for name in set(OP_NAME.findall(window_hlo)):
        path = _scopes(name)
        for outer, inner in zip(path, path[1:]):
            assert inner == outer or inner.startswith(outer + "."), name


def test_scope_names_match_no_layer_pattern():
    """The benchmark's layer patterns read the op_name line first: no scope
    name may match one, so the scopes claim nothing for any layer."""
    patterns = [
        re.compile(p)
        for path in sorted((ROOT / "bench" / "layers").glob("*.json"))
        for p in json.loads(path.read_text())["patterns"]
    ]
    assert patterns
    names = PHASES + ("pic.halo", "pic.migrate")
    hits = [(n, p.pattern) for n in names for p in patterns if p.search(n)]
    assert not hits, hits


def _recorded_bundles(monkeypatch):
    bundles = []
    real = simulation._fetch_bundle

    def fetch(bundle):
        host = real(bundle)
        bundles.append(host)
        return host

    monkeypatch.setattr(simulation, "_fetch_bundle", fetch)
    return bundles


def test_counters_total_the_bundles(monkeypatch):
    bundles = _recorded_bundles(monkeypatch)
    sim = _sim()
    sim.run(16, window=8)
    assert len(bundles) == 2
    sums = {k: 0 for k in ("n_moved", "n_alive", "n_ranked")}
    for host in bundles:
        per = host["per_step"]
        live = np.asarray(per["active"], bool)
        for k in sums:
            sums[k] += int(np.sum(np.asarray(per[k])[live]))
    assert (sim.moved, sim.particle_steps, sim.ranked) == (
        sums["n_moved"], sums["n_alive"], sums["n_ranked"])
    n = sim.state.particles.pos.shape[0]
    assert sim.ranked == 16 * n  # the rank sorts every key, every step
    assert 0 < sim.moved < sim.ranked
    assert sim.sorts + sim.rebuilds > 0, "no window sorted: the reason count is vacuous"
    assert sum(sim.sort_reasons.values()) == sim.sorts + sim.rebuilds
    assert set(sim.sort_reasons) <= set(REASON_NAMES)


def test_host_loop_counts_as_the_window():
    """The per-step host loop totals the same counters as the window."""
    wind, host = _sim(), _sim()
    wind.run(12, window=6)
    host.run(12, window=None)
    for k in ("sorts", "rebuilds", "moved", "particle_steps", "ranked", "sort_reasons"):
        assert getattr(host, k) == getattr(wind, k), k


def test_counters_survive_checkpoint(tmp_path):
    sim = _sim()
    sim.run(8, window=8)
    sim.save(str(tmp_path / "ck"))
    again = _sim()
    again.restore(str(tmp_path / "ck"))
    for k in ("moved", "particle_steps", "ranked", "sort_reasons"):
        assert getattr(again, k) == getattr(sim, k), k
    assert again.ranked > 0


def test_profile_holds_window_spans(tmp_path):
    from jax.profiler import ProfileData

    sim = _sim()
    sim.run(4, window=4)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        sim.run(4, window=4)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("pic.window"):
                    spans[ev.name] = {k: v for k, v in ev.stats}
    assert set(SPANS) <= set(spans), sorted(spans)
    for name in SPANS:
        assert spans[name]["step"] == 4 and spans[name]["k"] == 4, (name, spans[name])


def test_mesh_step_scopes_and_counters():
    """On a 2x2 mesh of forced CPU devices (a subprocess, as the other
    distributed checks): halo, migration and phase scopes in the window,
    and the counters total the bundles."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run(
        [sys.executable, str(ROOT / "tests" / "dist_scope_check.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0 and "SCOPES OK" in res.stdout, res.stdout + res.stderr
