"""Production training launcher: builds the mesh, installs sharding rules,
shards the train state, and runs the supervised loop.

On real hardware this is the per-process entrypoint (jax.distributed
initializes from the TPU pod environment); on CPU it runs with whatever
devices exist. The dry-run path (launch/dryrun.py) exercises the identical
cell construction against the 512-device production meshes.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-7b --smoke --steps 20
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.checkpoint import CheckpointManager
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.data import DataConfig, global_batch_at
from repro.distributed import FailureInjector, Supervisor
from repro.distributed.sharding import Rules, rules_for, use_rules
from repro.models.transformer import param_axes
from repro.optim import AdamWConfig, ScheduleConfig
from repro.train import TrainConfig, init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 -> (data, model) mesh")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch, dtype=jnp.bfloat16)

    mesh = None
    rules = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = jax.make_mesh(
            shape, ("data", "model")[: len(shape)],
            axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
        )
        table = rules_for(cfg, mode="train", multi_pod=False,
                          data_axis=shape[0], model_axis=shape[-1] if len(shape) > 1 else 1)
        rules = Rules(table, mesh)

    data = DataConfig(vocab_size=cfg.vocab_size, global_batch=args.global_batch, seq_len=args.seq)
    tcfg = TrainConfig(optimizer=AdamWConfig(lr=args.lr),
                       schedule=ScheduleConfig(warmup_steps=10, total_steps=args.steps))

    state = init_train_state(jax.random.PRNGKey(0), cfg)
    step = make_train_step(cfg, tcfg)

    if mesh is not None:
        pax = param_axes(cfg)
        put = lambda t, axes_tree: jax.tree.map(
            lambda x, a: jax.device_put(x, NamedSharding(mesh, rules.spec(a))), t, axes_tree,
            is_leaf=lambda n: isinstance(n, tuple) and all(isinstance(e, (str, type(None))) for e in n),
        )
        state = {
            "params": put(state["params"], pax),
            "opt": {"mu": put(state["opt"]["mu"], pax), "nu": put(state["opt"]["nu"], pax),
                    "count": state["opt"]["count"]},
            "step": state["step"],
        }

    jit_step = jax.jit(step)
    mgr = CheckpointManager(args.ckpt_dir, keep=3)

    def step_fn(st, i):
        batch = global_batch_at(i, data)
        return jit_step(st, batch)

    sup = Supervisor(step_fn, mgr, save_every=args.save_every)
    ctx = use_rules(rules) if rules else None
    if ctx:
        ctx.__enter__()
    try:
        if mesh is not None:
            with jax.set_mesh(mesh):
                state, _ = sup.run(state, args.steps)
        else:
            state, _ = sup.run(state, args.steps)
    finally:
        if ctx:
            ctx.__exit__(None, None, None)

    losses = [float(m["loss"]) for m in sup.metrics_log]
    print(f"steps={len(losses)} first_loss={losses[0]:.4f} last_loss={losses[-1]:.4f}")


if __name__ == "__main__":
    main()
