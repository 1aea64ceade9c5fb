"""Distributed loop-driver sweep: windowed in-shard_map scan vs per-step
host loop -> BENCH_dist.json.

Times `DistSimulation.run` end-to-end on a forced 8-host-device 4x2 mesh:
the per-step `make_dist_step` host loop (one stats sync + host policy
evaluation per step) against the device-resident windowed driver
(`make_dist_window`: the whole K-step scan inside ONE shard_map program,
psum-reduced in-graph policy, one fetched bundle per window):

    PYTHONPATH=src python -m benchmarks.run --only dist_sweep \
        --dist-json BENCH_dist.json [--scenario uniform]

The workload is spec-built from the scenario registry (`MeshSpec` selects
the distributed driver through the same `make_simulation` facade) and the
result row records the exact serialized `SimSpec` it measured.

The forced host-device override must be set before jax initializes, so this
module re-executes itself in a CPU-only subprocess (`JAX_PLATFORMS=cpu`, 8
emulated host devices) when the host does not expose 8 devices — decided
without importing jax, so the parent never holds a chip the child needs.
Both drivers run the identical shard_map step and identical policy
thresholds (wall-clock trigger disabled); the measured delta is loop
control flow: per-step dispatch of the sharded program + device->host stat
syncs vs one compiled window.

Schema: {"meta": {..., "scenario": name}, "results": {"incremental":
{host_us, device_us, speedup, spec}}, "acceptance":
{"dist_uniform_order2_speedup": x}}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

STEPS = 16
WINDOW = 8
ORDER = 2
MESH_SHAPE = (4, 2)
GRID = (8, 8, 16)
PPC_EACH_DIM = (2, 2, 2)
ROUNDS = 7
_CHILD_ENV = "_REPRO_DIST_SWEEP_CHILD"


def _needs_respawn() -> bool:
    # decided without importing jax: a parent holding the chip would starve
    # the child; with enough real devices the sweep runs in-process
    if os.environ.get(_CHILD_ENV) == "1":
        return False
    from repro.launch.devices import device_count_without_jax

    return device_count_without_jax() < MESH_SHAPE[0] * MESH_SHAPE[1]


def _respawn(json_path: str | None, scenario_name: str) -> None:
    from repro.launch.devices import emulated_devices_env

    env = emulated_devices_env(MESH_SHAPE[0] * MESH_SHAPE[1])
    env[_CHILD_ENV] = "1"
    cmd = [sys.executable, "-m", "benchmarks.dist_sweep", "--scenario", scenario_name]
    if json_path:
        cmd += ["--json", json_path]
    res = subprocess.run(cmd, env=env)
    if res.returncode != 0:
        raise RuntimeError(f"dist_sweep subprocess failed with code {res.returncode}")


def _make_spec(scenario_name: str):
    from repro.api import scenario
    from repro.core import SortPolicyConfig

    return scenario(
        scenario_name,
        grid=GRID,
        ppc_each_dim=PPC_EACH_DIM,
        u_thermal=0.05,
        perturb=None,  # plain thermal plasma: the workload every BENCH_dist.json measured
        order=ORDER,
        capacity=16,
        steps=STEPS,
        window=WINDOW,
        mesh=MESH_SHAPE,
        policy=SortPolicyConfig(sort_trigger_perf_enable=False),
    )


def _loop_thunk(sim, window: int | None):
    from repro.core import ResortPolicy, policy_init

    snap = (
        tuple(f.copy() for f in sim.fields),
        sim.pos.copy(), sim.u.copy(), sim.w.copy(), sim.alive.copy(),
        sim.slots.copy(), sim.pslot.copy(),
    )
    cfg0 = sim.config
    policy_cfg = sim.policy.config

    def thunk():
        # fresh run from the initial state each call (copies: the windowed
        # program donates its buffers); the reset cost is identical for both
        fields, pos, u, w, alive, slots, pslot = snap
        sim.fields = tuple(f.copy() for f in fields)
        sim.pos, sim.u, sim.w = pos.copy(), u.copy(), w.copy()
        sim.alive, sim.slots, sim.pslot = alive.copy(), slots.copy(), pslot.copy()
        sim.config = cfg0
        sim.policy = ResortPolicy(policy_cfg)
        sim.policy_state = policy_init()
        sim.sorts = sim.rebuilds = 0
        sim.halts = {}
        sim.retries = sim.restarts = sim.discarded_steps = 0
        sim._pending_presort = sim._pending_resume = False
        sim._host_step = 0
        sim.history = []
        sim.run(STEPS, window=window)
        return sim.fields[0]

    return thunk


def collect(*, label: str = "dist_sweep", scenario_name: str = "uniform") -> dict:
    import jax

    from benchmarks.common import emit, time_grid
    from repro.api import make_simulation

    spec = _make_spec(scenario_name)
    sim = make_simulation(spec)
    row = time_grid({
        "host": _loop_thunk(sim, None),
        "device": _loop_thunk(sim, WINDOW),
    }, rounds=ROUNDS)
    speedup = row["host"] / row["device"]
    emit(f"{label}/incremental/host", row["host"], f"{STEPS} steps per-step dist loop")
    emit(f"{label}/incremental/device", row["device"], f"window={WINDOW} speedup={speedup:.2f}x")

    n = GRID[0] * GRID[1] * GRID[2] * PPC_EACH_DIM[0] * PPC_EACH_DIM[1] * PPC_EACH_DIM[2]
    return {
        "meta": {
            "scenario": scenario_name,
            "grid": list(GRID),
            "mesh": list(MESH_SHAPE),
            "ppc_each_dim": list(PPC_EACH_DIM),
            "n_particles": n,
            "order": ORDER,
            "steps": STEPS,
            "window": WINDOW,
            "backend": jax.default_backend(),
            "n_devices": jax.device_count(),
            "note": (
                f"us per {STEPS}-step run, median over {ROUNDS} interleaved rounds "
                "(time_grid: drift-robust on shared CPUs); host = per-step "
                "make_dist_step loop with one stats sync + host policy per step, "
                "device = make_dist_window (whole scan inside shard_map, psum-reduced "
                "in-graph policy, one fetched bundle per window); identical step and "
                "sort decisions (perf trigger disabled) on both. 8 emulated host "
                "devices on one CPU: collective + dispatch costs are real, kernel "
                "parallelism is not — treat the trajectory, not one run, as signal. "
                "The result row embeds the exact serialized SimSpec it measured."
            ),
        },
        "results": {
            "incremental": {
                "host_us": row["host"],
                "device_us": row["device"],
                "speedup": speedup,
                # fault-tolerance counters of the final measured run
                # (docs/robustness.md): a clean benchmark run reports zeros —
                # any non-zero value means the timing absorbed rollback/replay
                # work and the row is not comparable to the trajectory
                "halts": dict(sim.halts),
                "retries": sim.retries,
                "restarts": sim.restarts,
                "discarded_steps": sim.discarded_steps,
                "spec": spec.to_dict(),
            },
        },
        # keyed by scenario so non-default workloads never masquerade as the
        # uniform baseline in the perf trajectory
        "acceptance": {f"dist_{scenario_name}_order2_speedup": speedup},
    }


def write_json(path: str, *, scenario_name: str = "uniform") -> None:
    if _needs_respawn():
        _respawn(path, scenario_name)
        return
    payload = collect(scenario_name=scenario_name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {path}")


def main(*, scenario_name: str = "uniform") -> None:
    if _needs_respawn():
        _respawn(None, scenario_name)
        return
    collect(scenario_name=scenario_name)


if __name__ == "__main__":
    argv = sys.argv[1:]
    name = argv[argv.index("--scenario") + 1] if "--scenario" in argv else "uniform"
    if "--json" in argv:
        write_json(argv[argv.index("--json") + 1], scenario_name=name)
    else:
        main(scenario_name=name)
