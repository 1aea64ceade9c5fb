"""Gradient-based design launcher: optimize SimSpec leaves by simulation.

    PYTHONPATH=src python -m repro.launch.pic_fit --scenario lwfa \\
        --objective injected_charge --learn laser.a0,laser.duration \\
        --steps 20 --iters 10 --lr 0.05
    PYTHONPATH=src python -m repro.launch.pic_fit --smoke   # CI grad lane

Builds the scenario's `SimSpec`, wraps it in a `GradSpec`
(--objective/--learn/--steps/--remat), and drives the AdamW loop of
`repro.grad.fit.fit_simulation` — printing one line per iteration and,
with ``--out``, writing the full trajectory (serialized spec included) as
JSON. ``--checkpoint DIR`` makes the fit resumable: re-running the same
command continues from the latest saved iteration.

``--smoke`` is the self-checking CI lane: a tiny LWFA fit (3 AdamW
iterations) asserting every gradient is finite, the loss decreases, and
the window compiled exactly once.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from repro.api import scenario, scenario_names
from repro.grad.fit import fit_simulation
from repro.grad.objectives import objective_names
from repro.grad.params import LEARNABLE
from repro.grad.spec import GradSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--scenario", default="lwfa",
                   help=f"registered scenario to optimize ({scenario_names()})")
    p.add_argument("--objective", default="injected_charge",
                   help=f"registered objective ({objective_names()})")
    p.add_argument("--learn", default="laser.a0",
                   help="comma-separated trainable SimSpec leaves "
                        f"({sorted(LEARNABLE)}; aliases laser.w0/laser.tau)")
    p.add_argument("--steps", type=int, default=0,
                   help="differentiated window length (0 = the spec's run.steps)")
    p.add_argument("--iters", type=int, default=8, help="AdamW iterations")
    p.add_argument("--remat", default="step", choices=("step", "chunk", "none"),
                   help="jax.checkpoint policy of the reverse pass")
    p.add_argument("--remat-chunk", type=int, default=0,
                   help="sub-window length for --remat chunk (0 = spec window)")
    p.add_argument("--objective-kw", action="append", default=[],
                   metavar="NAME=VALUE",
                   help="objective keyword override, repeatable (e.g. e_min=0.2)")
    # scenario shape overrides (the spec stays the source of truth)
    p.add_argument("--grid", type=int, nargs=3, default=None)
    p.add_argument("--ppc", type=int, default=None)
    p.add_argument("--order", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--capacity", type=int, default=None)
    # AdamW knobs
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--b1", type=float, default=0.9)
    p.add_argument("--b2", type=float, default=0.95)
    p.add_argument("--eps", type=float, default=1e-8)
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--grad-clip", type=float, default=1.0)
    # plumbing
    p.add_argument("--checkpoint", metavar="DIR", default=None,
                   help="resumable {params, optimizer} checkpoints under DIR")
    p.add_argument("--checkpoint-every", type=int, default=1)
    p.add_argument("--out", metavar="PATH", default=None,
                   help="write the fit trajectory (with serialized spec) as JSON")
    p.add_argument("--smoke", action="store_true",
                   help="run the self-checking tiny-LWFA grad lane and exit")
    return p


def _spec_overrides(args) -> dict:
    ov = {"backend": "xla"}  # the differentiable window requires XLA kernels
    if args.grid is not None:
        ov["grid"] = tuple(args.grid)
    if args.ppc is not None:
        ov["ppc"] = args.ppc
    if args.order is not None:
        ov["order"] = args.order
    if args.seed is not None:
        ov["seed"] = args.seed
    if args.capacity is not None:
        ov["capacity"] = args.capacity
    return ov


def _objective_kwargs(pairs) -> tuple:
    out = []
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--objective-kw wants NAME=VALUE, got {pair!r}")
        name, value = pair.split("=", 1)
        try:
            value = float(value)
        except ValueError:
            pass
        out.append((name, value))
    return tuple(out)


def run_fit(args) -> int:
    spec = scenario(args.scenario, **_spec_overrides(args))
    gspec = GradSpec(
        objective=args.objective,
        learn=tuple(args.learn.split(",")),
        steps=args.steps,
        remat=args.remat,
        remat_chunk=args.remat_chunk,
        objective_kwargs=_objective_kwargs(args.objective_kw),
    )
    opt = AdamWConfig(lr=args.lr, b1=args.b1, b2=args.b2, eps=args.eps,
                      weight_decay=args.weight_decay, grad_clip=args.grad_clip)

    def show(r):
        pstr = " ".join(f"{k}={v:.5g}" for k, v in r["params"].items())
        print(f"iter {r['iter']:3d}  objective={r['objective']:.6g}  "
              f"|grad|={r['grad_norm']:.3g}  {pstr}", flush=True)

    t0 = time.perf_counter()
    result = fit_simulation(
        spec, gspec, iters=args.iters, optimizer=opt,
        checkpoint_dir=args.checkpoint, checkpoint_every=args.checkpoint_every,
        on_iteration=show,
    )
    elapsed = time.perf_counter() - t0
    print(f"fit: {len(result.history)} iterations in {elapsed:.2f}s, "
          f"{result.compiles} window trace(s); final "
          + " ".join(f"{k}={v:.6g}" for k, v in result.params.items()))
    if args.out:
        payload = {
            "spec": spec.to_dict(),
            "grad": result.grad.to_dict(),
            "optimizer": vars(opt) if not hasattr(opt, "__dataclass_fields__")
            else {f: getattr(opt, f) for f in opt.__dataclass_fields__},
            "iters": args.iters,
            "history": result.history,
            "final_params": result.params,
            "compiles": result.compiles,
            "elapsed_s": elapsed,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"wrote {args.out}")
    return 0


def run_smoke() -> int:
    """Tiny LWFA fit, 3 AdamW iterations: finite grads, decreasing loss,
    one window compile. The CI grad lane."""
    import math

    spec = scenario("lwfa", grid=(6, 6, 24), ppc=1, backend="xla")
    t0 = time.perf_counter()
    result = fit_simulation(
        spec, learn=("laser.a0",), steps=6, iters=3,
        objective_kwargs={"e_min": 0.1},
    )
    elapsed = time.perf_counter() - t0
    ok = True
    for r in result.history:
        if not all(math.isfinite(g) for g in r["grads"].values()):
            print(f"FAIL: iteration {r['iter']} has non-finite grads: {r['grads']}")
            ok = False
    losses = [r["loss"] for r in result.history]
    if not losses[-1] < losses[0]:
        print(f"FAIL: loss did not decrease over the fit: {losses}")
        ok = False
    if result.compiles != 1:
        print(f"FAIL: window traced {result.compiles} times (wanted exactly 1)")
        ok = False
    print(f"pic_fit smoke: {len(losses)} iters, objective "
          f"{result.history[0]['objective']:.4g} -> {result.history[-1]['objective']:.4g}, "
          f"{result.compiles} compile(s), {elapsed:.2f}s -> {'OK' if ok else 'FAIL'}")
    return 0 if ok else 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        return run_smoke()
    return run_fit(args)


if __name__ == "__main__":
    sys.exit(main())
