"""Communication co-design sweep: overlapped halos x compressed migration
x load-aware repartitioning -> BENCH_comm.json.

Times `DistSimulation.run` on a forced 8-host-device 4x2 mesh across the
`CommSpec` matrix (docs/distributed.md "Communication co-design"):

  uniform workload    serialized | overlap | compress | overlap+compress
                      (balanced thermal plasma: the halo/migration paths
                      with no load skew — overlap must not regress, and is
                      bit-identical by construction)
  imbalanced LWFA     serialized | overlap+rebalance
                      (every particle starts in one x-slab of the 4x2
                      decomposition: 2 of 8 shards hold all the load, and
                      every shard's particle arrays are padded to the
                      straggler's occupancy. The rebalance variant is timed
                      in the steady state AFTER its HALT_IMBALANCE re-split
                      — the honest comparison is the decomposition the
                      planner chose vs the static imbalanced one, not the
                      one-off re-split cost, which is a host gather +
                      recompile paid once per load-shape change.)

    PYTHONPATH=src python -m benchmarks.run --only comm_sweep \
        --comm-json BENCH_comm.json

The forced host-device override must be set before jax initializes, so this
module re-executes itself in a CPU-only subprocess (`JAX_PLATFORMS=cpu`, 8
emulated host devices) when the host does not expose 8 devices — decided
without importing jax, so the parent never holds a chip the child needs.
Rows embed the serialized `SimSpec` measured where the workload is
spec-expressible (the imbalanced slab is carved from the lwfa scenario's
particle set by an alive-mask — recorded in meta).

Schema: {"meta": {...}, "results": {"uniform": {<variant>: {us, speedup,
spec}}, "imbalanced_lwfa": {...}}, "acceptance": {...}}
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
GRID = (16, 8, 16)
ROUNDS = 5
_CHILD_ENV = "_REPRO_COMM_SWEEP_CHILD"

UNIFORM_VARIANTS = {
    "serialized": {},
    "overlap": {"overlap_halo": True},
    "compress": {"compress_migration": True},
    "overlap_compress": {"overlap_halo": True, "compress_migration": True},
}
IMBALANCED_VARIANTS = {
    "serialized": {},
    "overlap_rebalance": {"overlap_halo": True, "rebalance_enable": True,
                          "imbalance_ratio": 2.0},
}


def _needs_respawn(n: int | None = None) -> bool:
    # decided without importing jax: a parent holding the chip would starve
    # the child; with enough real devices the sweep runs in-process
    if os.environ.get(_CHILD_ENV) == "1":
        return False
    from repro.launch.devices import device_count_without_jax

    return device_count_without_jax() < (n or MESH_SHAPE[0] * MESH_SHAPE[1])


def _respawn(json_path: str | None, *, smoke: bool = False, n: int | None = None) -> None:
    from repro.launch.devices import emulated_devices_env

    env = emulated_devices_env(n or MESH_SHAPE[0] * MESH_SHAPE[1])
    env[_CHILD_ENV] = "1"
    cmd = [sys.executable, "-m", "benchmarks.comm_sweep"]
    if smoke:
        cmd += ["--smoke"]
    if json_path:
        cmd += ["--json", json_path]
    res = subprocess.run(cmd, env=env)
    if res.returncode != 0:
        raise RuntimeError(f"comm_sweep subprocess failed with code {res.returncode}")


def _make_spec(scenario_name: str, comm: dict):
    from repro.api import scenario
    from repro.core import SortPolicyConfig

    kw = dict(
        grid=GRID,
        order=ORDER,
        steps=STEPS,
        window=WINDOW,
        mesh=MESH_SHAPE,
        policy=SortPolicyConfig(sort_trigger_perf_enable=False),
    )
    if comm:
        kw["comm"] = comm
    if scenario_name == "uniform":
        kw.update(ppc_each_dim=(2, 2, 2), u_thermal=0.05, perturb=None)
    return scenario(scenario_name, **kw)


def _make_sim(spec, imbalanced: bool):
    """Spec-built sim; for the imbalanced workload the lwfa particle set is
    carved down to the first x-shard column (x < GRID[0]/MESH_SHAPE[0]) so
    2 of the 8 shards start with ALL the load."""
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.api import build_fields, build_particles, make_simulation

    if not imbalanced:
        return make_simulation(spec)
    parts = build_particles(spec)
    keep = jnp.asarray(np.asarray(parts.pos)[:, 0] < GRID[0] / MESH_SHAPE[0])
    parts = dataclasses.replace(parts, alive=parts.alive & keep)
    return make_simulation(spec, particles=parts, fields=build_fields(spec))


def _steady_thunk(sim, *, warmup_steps: int):
    """Warm `warmup_steps` (compiles; a rebalance-enabled driver re-splits
    here), snapshot, then time STEPS-step continuations from that snapshot —
    every variant of a workload times the same post-warmup phase."""
    sim.run(warmup_steps, window=WINDOW)
    snap = (
        tuple(f.copy() for f in sim.fields),
        sim.pos.copy(), sim.u.copy(), sim.w.copy(), sim.alive.copy(),
        sim.slots.copy(), sim.pslot.copy(),
        sim.slab_d.copy(), sim.slab_valid.copy(),
    )
    step0 = sim._host_step

    def thunk():
        (fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid) = snap
        sim.fields = tuple(f.copy() for f in fields)
        sim.pos, sim.u, sim.w = pos.copy(), u.copy(), w.copy()
        sim.alive, sim.slots, sim.pslot = alive.copy(), slots.copy(), pslot.copy()
        sim.slab_d, sim.slab_valid = slab_d.copy(), slab_valid.copy()
        sim.mid_pos = sim.mid_pos * 0
        sim.mid_u = sim.mid_u * 0
        sim._pending_presort = sim._pending_resume = False
        sim._host_step = step0
        sim.history = []
        sim.run(STEPS, window=WINDOW)
        return sim.fields[0]

    return thunk


def collect(*, label: str = "comm_sweep") -> dict:
    import jax
    import numpy as np

    from benchmarks.common import emit, time_grid

    results: dict = {}
    acceptance: dict = {}
    notes: dict = {}

    for workload, variants, scenario_name in (
        ("uniform", UNIFORM_VARIANTS, "uniform"),
        ("imbalanced_lwfa", IMBALANCED_VARIANTS, "lwfa"),
    ):
        sims, specs = {}, {}
        for name, comm in variants.items():
            spec = _make_spec(scenario_name, comm)
            sims[name] = _make_sim(spec, imbalanced=(workload == "imbalanced_lwfa"))
            specs[name] = spec
        thunks = {
            name: _steady_thunk(sim, warmup_steps=STEPS) for name, sim in sims.items()
        }
        if workload == "uniform":
            # the overlap path must be bit-identical to serialized: compare
            # the post-warmup field state before timing perturbs it further
            f0 = np.asarray(sims["serialized"].fields[0])
            np.testing.assert_array_equal(f0, np.asarray(sims["overlap"].fields[0]))
        row = time_grid(thunks, rounds=ROUNDS)
        results[workload] = {}
        for name in variants:
            sim = sims[name]
            speedup = row["serialized"] / row[name]
            results[workload][name] = {
                "us": row[name],
                "speedup_vs_serialized": speedup,
                "comm_stats": dict(sim.comm_stats),
                "rebalances": sim.growths.get("rebalance", 0),
                "mesh": [sim.sx, sim.sy],
                "n_local": sim.n_local,
                "halts": dict(sim.halts),
                "spec": specs[name].to_dict(),
            }
            emit(f"{label}/{workload}/{name}", row[name],
                 f"speedup={speedup:.2f}x mesh={sim.sx}x{sim.sy} "
                 f"migrated={sim.comm_stats['n_migrated']}")
            acceptance[f"comm_{workload}_{name}_speedup"] = speedup
        notes[workload] = {n: row[n] for n in variants}

    reb = results["imbalanced_lwfa"]["overlap_rebalance"]
    assert reb["rebalances"] >= 1, (
        f"imbalanced workload never triggered a rebalance: {reb}"
    )

    return {
        "meta": {
            "grid": list(GRID),
            "mesh": list(MESH_SHAPE),
            "order": ORDER,
            "steps": STEPS,
            "window": WINDOW,
            "rounds": ROUNDS,
            "backend": jax.default_backend(),
            "n_devices": jax.device_count(),
            "note": (
                f"us per {STEPS}-step run, median over {ROUNDS} interleaved "
                "rounds (time_grid), timed from a common post-warmup snapshot "
                "per workload: rebalance-enabled drivers re-split during the "
                "warmup, so their rows measure the steady state of the planner-"
                "chosen decomposition (re-split cost = one host gather + "
                "recompile, paid once per load-shape change, excluded like "
                "every other compile). imbalanced_lwfa carves the lwfa "
                "particle set down to x < nx/sx (2 of 8 shards hold all load; "
                "the spec rows record the pre-carve scenario). 8 emulated "
                "host devices on one CPU: collective + dispatch + padded-"
                "array costs are real, device parallelism is not — the "
                "rebalance win here is the n_local shrink, not straggler "
                "elimination; treat the trajectory, not one run, as signal."
            ),
        },
        "results": results,
        "acceptance": acceptance,
    }


def smoke() -> None:
    """CI drift guard: a 6-step 2x2-mesh run with the overlapped halo
    exchange must be BIT-identical to the serialized exchange (fields,
    positions, momenta) — run.py --smoke calls this (4 forced host devices
    in a subprocess so the override never leaks)."""
    if _needs_respawn(4):
        _respawn(None, smoke=True, n=4)
        return
    import numpy as np

    from benchmarks.common import emit
    from repro.api import make_simulation, scenario
    from repro.core import SortPolicyConfig

    def run(comm):
        kw = dict(
            grid=(8, 8, 8), ppc_each_dim=(2, 2, 2), u_thermal=0.2, perturb=None,
            order=2, steps=6, window=3, mesh=(2, 2),
            policy=SortPolicyConfig(sort_trigger_perf_enable=False),
        )
        if comm:
            kw["comm"] = comm
        sim = make_simulation(scenario("uniform", **kw))
        sim.run(6)
        return sim

    base = run({})
    over = run({"overlap_halo": True})
    for fa, fb in zip(base.fields, over.fields):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    np.testing.assert_array_equal(np.asarray(base.pos), np.asarray(over.pos))
    np.testing.assert_array_equal(np.asarray(base.u), np.asarray(over.u))
    assert base.diagnostics() == over.diagnostics()
    emit("smoke/comm_sweep/overlap_bit_identity", 0.0, "overlap==serialized bitwise")


def write_json(path: str) -> None:
    if _needs_respawn():
        _respawn(path)
        return
    payload = collect()
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"wrote {path}")


def main() -> None:
    if _needs_respawn():
        _respawn(None)
        return
    collect()


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--smoke" in argv:
        smoke()
    elif "--json" in argv:
        write_json(argv[argv.index("--json") + 1])
    else:
        main()
