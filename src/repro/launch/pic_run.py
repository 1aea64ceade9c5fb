"""PIC simulation launcher: every registered scenario from one binary.

    PYTHONPATH=src python -m repro.launch.pic_run --scenario uniform --steps 50
    PYTHONPATH=src python -m repro.launch.pic_run --scenario two_stream --steps 50
    PYTHONPATH=src python -m repro.launch.pic_run --scenario lwfa --mesh 2x2
    PYTHONPATH=src python -m repro.launch.pic_run --spec myrun.json
    PYTHONPATH=src python -m repro.launch.pic_run --scenario weibel --dump-spec weibel.json
    PYTHONPATH=src python -m repro.launch.pic_run --scenario uniform --ensemble 4
    PYTHONPATH=src python -m repro.launch.pic_run --scenario two_stream \\
        --sweep drift=0.1,0.2,0.3 --ensemble 2

The run is described by a `repro.api.SimSpec`: ``--scenario NAME`` builds
it from the registry, ``--spec FILE.json`` loads a serialized one, and the
remaining flags are overrides applied onto that spec (the pre-SimSpec
flags — ``--workload``, ``--steps``, ``--order``, ... — keep working as
shims that build a spec). NOTE: scenario defaults were unified in the
migration — ``lwfa`` now means the canonical registry scenario (the
`examples/lwfa.py` laser/dt/step parameters), not this launcher's old
ad-hoc variant, so a bare ``--workload lwfa`` reproduces the example, not
pre-migration launcher output (pin dt/steps/etc. via flags or --spec to
compare against old runs). `repro.api.make_simulation` then yields the
single-device windowed driver or, when the spec (or ``--mesh``) names a
device mesh, the distributed shard_map driver — same facade either way.
"""

from __future__ import annotations

import argparse
import time

from repro.launch.devices import (
    force_host_devices,
    parse_mesh,
    peek_mesh_argv,
    peek_spec_mesh_argv,
)

# a mesh of SXxSY shards needs SX*SY devices, which can only be forced
# BEFORE jax import — peek argv (and any --spec file's mesh entry) now;
# repro.launch.devices is jax-free on purpose
_MESH_ARGV = peek_mesh_argv() or peek_spec_mesh_argv()
if _MESH_ARGV is not None:
    force_host_devices(_MESH_ARGV[0] * _MESH_ARGV[1])

from repro.api import SimSpec, make_simulation, scenario, scenario_names  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def parse_fault(text: str) -> dict:
    """``KIND:STEP[:COMPONENT[:COUNT]]`` -> FaultSpec override dict, e.g.
    ``nan_field:40:ez`` or ``crash:100`` or ``nan_momentum:10::0``
    (count=0 = persistent)."""
    parts = text.split(":")
    if len(parts) < 2:
        raise ValueError(f"--fault wants KIND:STEP[:COMPONENT[:COUNT]], got {text!r}")
    out = {"kind": parts[0], "step": int(parts[1])}
    if len(parts) > 2 and parts[2]:
        out["component"] = parts[2]
    if len(parts) > 3 and parts[3]:
        out["count"] = int(parts[3])
    return out


def _sweep_value(text: str):
    """Sweep values parse as int, then float, then bare string."""
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            pass
    return text


def parse_sweeps(texts) -> dict:
    """Repeated ``--sweep PARAM=V1,V2,...`` flags -> `EnsembleSpec.sweep`
    axes, with PARAM validated against the registry's flat override
    vocabulary (the same names every other flag routes through)."""
    from repro.api.registry import _OVERRIDE_PATHS

    axes: dict[str, list] = {}
    for text in texts:
        name, sep, values = text.partition("=")
        if not sep or not values:
            raise ValueError(f"--sweep wants PARAM=V1,V2,..., got {text!r}")
        if name not in _OVERRIDE_PATHS:
            raise ValueError(
                f"--sweep {name}: not a flat override "
                f"(one of {sorted(_OVERRIDE_PATHS)})"
            )
        if name in axes:
            raise ValueError(f"--sweep {name}: axis given twice")
        axes[name] = [_sweep_value(v) for v in values.split(",")]
    return axes


def build_spec(args) -> SimSpec:
    """Scenario/spec-file + flag overrides -> the SimSpec to run."""
    overrides = {}
    if args.steps is not None:
        overrides["steps"] = args.steps
    if args.window is not None:
        overrides["window"] = args.window
    if args.ppc is not None:
        overrides["ppc"] = args.ppc
    if args.order is not None:
        overrides["order"] = args.order
    if args.deposition is not None:
        overrides["deposition"] = args.deposition
    if args.gather is not None:
        overrides["gather"] = args.gather
    if args.sort is not None:
        overrides["sort"] = args.sort
    if args.mesh is not None:
        overrides["mesh"] = parse_mesh(args.mesh)
    if args.use_pallas:
        overrides["use_pallas"] = True
    if args.backend is not None:
        overrides["backend"] = args.backend
    if args.sentinel:
        overrides["health"] = {"enable": True}
    if args.autosave_every is not None:
        overrides["autosave_every"] = args.autosave_every
    if args.autosave_path is not None:
        overrides["autosave_path"] = args.autosave_path
    if args.fault is not None:
        overrides["fault"] = parse_fault(args.fault)
    comm = {}
    if args.overlap_halo:
        comm["overlap_halo"] = True
    if args.compress_migration:
        comm["compress_migration"] = True
    if args.rebalance:
        comm["rebalance_enable"] = True
    if args.imbalance_ratio is not None:
        comm["imbalance_ratio"] = args.imbalance_ratio
    if comm:
        overrides["comm"] = comm

    if args.spec is not None:
        try:
            with open(args.spec) as f:
                spec = SimSpec.from_json(f.read())
        except (OSError, ValueError, TypeError, KeyError) as e:
            raise SystemExit(f"--spec {args.spec}: {e}") from e
        if args.grid is not None:
            overrides["grid"] = tuple(args.grid)
        from repro.api import apply_overrides

        return apply_overrides(spec, **overrides)

    name = args.scenario or args.workload or "uniform"
    if args.grid is not None:
        overrides["grid"] = tuple(args.grid)
    return scenario(name, **overrides)


def run_ensemble(ensemble) -> None:
    """Batched path: bucket the members by compiled shape, run every bucket,
    print a per-member summary (docs/ensemble.md)."""
    from repro.api import make_ensemble

    t0 = time.perf_counter()
    ens = make_ensemble(ensemble)
    build_dt = time.perf_counter() - t0
    n = ens.n_members
    buckets = len(ens.sims)
    print(
        f"{ensemble.base.name}: ensemble of {n} members in {buckets} "
        f"shape bucket{'s' if buckets != 1 else ''} "
        f"({[s.n_members for s in ens.sims]} members/bucket), built in {build_dt:.2f}s"
    )
    t0 = time.perf_counter()
    ens.run()
    run_dt = time.perf_counter() - t0
    steps = [m.run.steps for m in ens.members]
    print(f"{sum(steps)} member-steps in {run_dt:.2f}s "
          f"({n / run_dt:.2f} members/s)")
    for i, d in enumerate(ens.diagnostics()):
        print(
            f"  member {i} ({ens.members[i].name}): step {d['step']}, "
            f"field={d['field_energy']:.4e} kinetic={d['kinetic_energy']:.4e} "
            f"total={d['total_energy']:.4e}, n_alive={d['n_alive']}"
        )


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    src = ap.add_argument_group("run selection")
    src.add_argument("--scenario", default=None, metavar="NAME",
                     help=f"registered scenario to run (one of {scenario_names()}; default uniform)")
    src.add_argument("--spec", default=None, metavar="FILE.json",
                     help="load a serialized SimSpec instead of a named scenario")
    src.add_argument("--dump-spec", default=None, metavar="PATH",
                     help="write the resolved SimSpec JSON to PATH and exit (provenance / editing)")
    ov = ap.add_argument_group("spec overrides (deprecated shims from the pre-SimSpec CLI)")
    ov.add_argument("--workload", choices=["uniform", "lwfa"], default=None,
                    help="deprecated alias of --scenario")
    ov.add_argument("--steps", type=int, default=None)
    ov.add_argument("--ppc", type=int, default=None, help="particles per cell per dim")
    ov.add_argument("--order", type=int, default=None, choices=[1, 2, 3])
    ov.add_argument("--deposition", choices=["scatter", "rhocell", "matrix", "matrix_unfused"], default=None)
    ov.add_argument("--gather", choices=["matrix", "matrix_unfused", "scatter"], default=None,
                    help="field-gather mode (default: auto-paired — fused matrix for bin depositions)")
    ov.add_argument("--sort", choices=["incremental", "rebuild", "global", "none"], default=None)
    ov.add_argument("--grid", type=int, nargs=3, default=None)
    ov.add_argument("--use-pallas", action="store_true", dest="use_pallas",
                    help="deprecated: same as --backend pallas")
    ov.add_argument("--backend", choices=["auto", "xla", "pallas", "pallas_reduced"], default=None,
                    help="kernel-dispatch backend for the bin contractions "
                    "(auto = benchmark-to-select with persisted autotune cache)")
    ov.add_argument(
        "--window", type=int, default=None,
        help="device-resident loop: steps per compiled scan window (one host "
        "sync per window); 0 = legacy host-driven per-step loop",
    )
    ov.add_argument(
        "--mesh", type=str, default=None, metavar="SXxSY",
        help="run domain-decomposed on an SXxSY device mesh (DistSimulation); "
        "forces SX*SY host devices when no accelerator override is present",
    )
    ens = ap.add_argument_group("ensembles (docs/ensemble.md)")
    ens.add_argument("--ensemble", type=int, default=None, metavar="N",
                     help="run N seed-staggered replicas of the spec as one "
                     "batched ensemble (with --sweep: N replicas per sweep point)")
    ens.add_argument("--sweep", action="append", default=None,
                     metavar="PARAM=V1,V2,...",
                     help="repeatable: one cartesian sweep axis over a flat "
                     "override (e.g. --sweep density=0.5,1.0 --sweep order=1,2); "
                     "members with the same compiled shape share one executable")
    cm = ap.add_argument_group("distributed communication (docs/distributed.md)")
    cm.add_argument("--overlap-halo", action="store_true", dest="overlap_halo",
                    help="issue halo-exchange collectives overlapped with interior "
                    "compute (bit-identical to the serialized exchange)")
    cm.add_argument("--compress-migration", action="store_true", dest="compress_migration",
                    help="quantize migration payloads (uint16 fixed-point positions, "
                    "bf16 momenta; weights stay exact f32)")
    cm.add_argument("--rebalance", action="store_true",
                    help="load-aware repartitioning: halt the window when shard "
                    "occupancy imbalance exceeds --imbalance-ratio and re-split "
                    "the domain decomposition")
    cm.add_argument("--imbalance-ratio", type=float, default=None, metavar="R",
                    help="rebalance trigger: max shard occupancy > R x the "
                    "balanced share (default 4.0)")
    ft = ap.add_argument_group("fault tolerance (docs/robustness.md)")
    ft.add_argument("--sentinel", action="store_true",
                    help="enable the in-graph health sentinel (NaN/Inf + "
                    "charge/energy invariants) and the rollback-and-retry supervisor")
    ft.add_argument("--autosave-every", type=int, default=None, metavar="N",
                    help="checkpoint every N steps (and at entry/exit); a hard "
                    "crash restores the latest autosave and resumes")
    ft.add_argument("--autosave-path", type=str, default=None, metavar="DIR",
                    help="autosave directory (default: checkpoints/<scenario>)")
    ft.add_argument("--fault", type=str, default=None, metavar="KIND:STEP[:COMP[:COUNT]]",
                    help="chaos harness: inject a deterministic fault, e.g. "
                    "nan_field:40:ez, charge_scale:10, recv_drop:25, crash:100")
    args = ap.parse_args()
    if (args.scenario or args.workload) and args.spec:
        ap.error("--scenario/--workload and --spec are mutually exclusive")
    if args.workload:
        print(
            "note: --workload is deprecated, use --scenario (scenario defaults were "
            "unified: 'lwfa' now runs the canonical registry parameters, not the old "
            "launcher variant — see the module docstring)"
        )

    try:
        spec = build_spec(args)
        ensemble = None
        if args.ensemble is not None or args.sweep:
            from repro.api import EnsembleSpec

            if args.sweep:
                ensemble = EnsembleSpec.sweep(
                    spec, parse_sweeps(args.sweep), replicas=args.ensemble or 1
                )
            else:
                ensemble = EnsembleSpec.replicate(spec, args.ensemble)
    except (ValueError, TypeError, KeyError) as e:
        ap.error(str(e))  # spec validation failures -> one-line message, not a traceback
    if args.dump_spec:
        with open(args.dump_spec, "w") as f:
            f.write(spec.to_json() if ensemble is None else ensemble.to_json())
        print(f"wrote {args.dump_spec}")
        return
    if ensemble is not None:
        run_ensemble(ensemble)
        return

    sim = make_simulation(spec)
    n_steps = spec.run.steps
    window = spec.run.window or None
    loop = f"device-resident scan (window={window})" if window else "host-driven per-step loop"
    mesh_note = f", mesh {spec.mesh.shape[0]}x{spec.mesh.shape[1]}" if spec.mesh.shape else ""
    n_parts = int(sim.diagnostics()["n_alive"])
    print(
        f"{spec.name}: grid {spec.grid.shape}, {n_parts} particles, order "
        f"{spec.deposition.order}, {spec.deposition.mode}/{spec.sort.mode}, {loop}{mesh_note}"
    )

    # one warmup compile: the windowed driver pads every window (including
    # tails) to the same static length, so a single run covers the program
    if window:
        sim.run(min(window, n_steps))
    else:
        sim.run(2)
    t0 = time.perf_counter()
    sim.run(n_steps)
    dt = time.perf_counter() - t0
    d = sim.diagnostics()
    n_alive = d["n_alive"]
    print(
        f"{n_steps} steps in {dt:.2f}s ({n_alive * n_steps / dt:.3e} particle-steps/s); "
        f"sorts={sim.sorts} rebuilds={sim.rebuilds}"
    )
    print(f"energies: field={d['field_energy']:.4e} kinetic={d['kinetic_energy']:.4e} total={d['total_energy']:.4e}")


if __name__ == "__main__":
    main()
