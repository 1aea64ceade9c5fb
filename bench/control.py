#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the numbers that
`check.compare` gives for the program as configured and for its control,
on several seeds, at a cell's own size, in one process.

    python bench/control.py --workload uniform.o3 --seeds 11,12,13 --variants program,control

``program`` runs the cell as the benchmark does. ``control`` runs it with
every bin contraction computed at ``Precision.HIGH`` (three bf16 passes),
the next precision below the configuration's float32 at ``HIGHEST``: the
program's own ``CONTRACTION_PRECISION`` switch, set in each module that
imports it. ``altered`` adds 0.05 to one particle's momentum where the
push produces it, every step. JAX's caches are cleared around each
variant so the window compiles again. Each run is the benchmark's own
path with a measured window of one window's length. Prints one JSON line
per run; without a TPU it exits 3.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

#: modules that import the contraction precision by value
PRECISION_MODULES = (
    "repro.core.shape_functions", "repro.core.deposition", "repro.core.gather",
    "repro.core.matrix_scatter", "repro.kernels.dispatch",
    "repro.kernels.deposition.kernel", "repro.kernels.deposition.ref",
    "repro.kernels.gather.kernel", "repro.kernels.gather.ref",
    "repro.kernels.scatter_matrix.kernel", "repro.kernels.scatter_matrix.ref",
)


def set_contraction_precision(precision) -> dict:
    """Set ``CONTRACTION_PRECISION`` wherever the program holds it; returns
    the previous values, by module, for `restore`."""
    import importlib

    import jax

    before = {}
    for name in PRECISION_MODULES:
        module = importlib.import_module(name)
        if hasattr(module, "CONTRACTION_PRECISION"):
            before[name] = module.CONTRACTION_PRECISION
            module.CONTRACTION_PRECISION = precision
    jax.clear_caches()
    return before


def restore(before: dict) -> None:
    import importlib

    import jax

    for name, value in before.items():
        importlib.import_module(name).CONTRACTION_PRECISION = value
    jax.clear_caches()


def alter_momentum():
    """Plant a fault where the push produces momenta; returns the undo."""
    import jax

    from repro.pic import simulation

    real = simulation.boris_push

    def altered(u, e, b, q_over_m, dt):
        return real(u, e, b, q_over_m, dt).at[0, 0].add(0.05)

    simulation.boris_push = altered
    jax.clear_caches()

    def undo():
        simulation.boris_push = real
        jax.clear_caches()

    return undo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--variants", default="program,control")
    args = ap.parse_args(argv)

    bench = run.load_benchmark()
    cell = run.find_cell(bench, args.workload)
    config = run.load_named("configs", cell["config"])
    traffic = run.load_named("traffic", cell["traffic"])
    limits = run.load_named("limits", args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    devices = run.require_chips(int(cell["chips"]))
    import jax

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    for variant in args.variants.split(","):
        before, undo = {}, None
        if variant == "control":
            before = set_contraction_precision(jax.lax.Precision.HIGH)
        elif variant == "altered":
            undo = alter_momentum()
        elif variant != "program":
            raise ValueError(f"unknown variant {variant!r}")
        for seed in (int(s) for s in args.seeds.split(",")):
            line = run.run_cell(args.workload, config, traffic, seed=seed, seconds=0.0,
                                trace=False, devices=devices, limits=limits, per_layer=[], log=log)
            print(json.dumps({"variant": variant, "seed": seed, "correct": line["correct"],
                              "info": line["info"], "checks": line["checks"]}), flush=True)
        restore(before)
        if undo is not None:
            undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
