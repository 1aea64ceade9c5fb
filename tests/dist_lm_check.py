"""Standalone distributed-LM checks on 8 fake CPU devices (subprocess-only):

  A. sharded train step (2x2 (data, model) mesh, logical rules: FSDP + TP +
     SP + EP) == single-device train step, loss-exact to fp32 tolerance;
  B. GPipe pipeline-parallel forward == sequential stage composition;
  C. int8 error-feedback compressed DP training converges like exact psum.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.data import DataConfig, global_batch_at  # noqa: E402
from repro.distributed.compression import compressed_psum_grads, exact_pmean_grads, zeros_like_residual  # noqa: E402
from repro.distributed.pipeline import pipeline_forward  # noqa: E402
from repro.distributed.sharding import Rules, train_rules, tree_specs, use_rules  # noqa: E402
from repro.models import LayerSpec, ModelConfig, MoEConfig  # noqa: E402
from repro.models.transformer import param_axes  # noqa: E402
from repro.optim import AdamWConfig, ScheduleConfig  # noqa: E402
from repro.train import TrainConfig, init_train_state, make_train_step  # noqa: E402

CFG = ModelConfig(
    name="tiny_moe", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
    vocab_size=64, pattern=(LayerSpec("attn", "moe"),),
    moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=4.0),
)
DATA = DataConfig(vocab_size=64, global_batch=8, seq_len=32, seed=0)
TCFG = TrainConfig(optimizer=AdamWConfig(lr=1e-3), schedule=ScheduleConfig(warmup_steps=2, total_steps=50))


def check_sharded_train_step():
    state = init_train_state(jax.random.PRNGKey(0), CFG)
    step = make_train_step(CFG, TCFG)

    # single device reference
    ref_state, ref_m = jax.jit(step)(state, global_batch_at(0, DATA))

    mesh = jax.make_mesh((2, 2), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = Rules(train_rules(multi_pod=False), mesh)
    axes = {"params": param_axes(CFG)}
    pspecs = tree_specs(axes["params"], rules)

    def put(tree, specs):
        return jax.tree.map(lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)

    sh_state = {
        "params": put(state["params"], pspecs),
        "opt": {
            "mu": put(state["opt"]["mu"], pspecs),
            "nu": put(state["opt"]["nu"], pspecs),
            "count": jax.device_put(state["opt"]["count"], NamedSharding(mesh, P())),
        },
        "step": jax.device_put(state["step"], NamedSharding(mesh, P())),
    }
    batch = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(("data",), None))), global_batch_at(0, DATA)
    )
    with jax.set_mesh(mesh), use_rules(rules):
        got_state, got_m = jax.jit(step)(sh_state, batch)
        jax.block_until_ready(got_state)

    ref_loss, got_loss = float(ref_m["loss"]), float(got_m["loss"])
    assert abs(ref_loss - got_loss) / ref_loss < 1e-4, (ref_loss, got_loss)
    # parameters after one update agree
    for a, b in zip(jax.tree.leaves(ref_state["params"]), jax.tree.leaves(got_state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5)
    print(f"A sharded-train-step OK loss={got_loss:.4f}")


def check_pipeline_parallel():
    n_stages, n_micro, mb, d = 4, 8, 4, 16
    key = jax.random.PRNGKey(1)
    w = jax.random.normal(key, (n_stages, d, d)) * 0.3

    def stage_fn(wi, x):
        return jnp.tanh(x @ wi)

    x = jax.random.normal(key, (n_micro, mb, d))
    mesh = jax.make_mesh((n_stages,), ("pipe",), axis_types=(jax.sharding.AxisType.Auto,) * 1)
    got = pipeline_forward(w, x, stage_fn, mesh=mesh)

    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)
    print("B pipeline-parallel OK")


def check_compressed_dp():
    from repro.optim import adamw_init, adamw_update

    mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,) * 1)
    k = jax.random.PRNGKey(2)
    w0 = jax.random.normal(k, (16, 16)) * 0.3

    w_true = jax.random.normal(jax.random.PRNGKey(9), (16, 16)) * 0.5

    def local_loss(w, x):
        y = x @ w_true  # linearly-realizable target
        pred = x @ w
        return jnp.mean((pred - y) ** 2)

    def make_run(compress: bool):
        def dp_step(w, opt, res, x_shard):
            def body(w, res, x):
                g = jax.grad(local_loss)(w, x)
                if compress:
                    g, res = compressed_psum_grads(g, res, "data")
                else:
                    g = exact_pmean_grads(g, "data")
                return g, res

            g, res = jax.shard_map(
                body, mesh=mesh, in_specs=(P(), P(), P("data")), out_specs=(P(), P()), check_vma=False
            )(w, res, x_shard)
            w, opt, _ = adamw_update(g, opt, w, AdamWConfig(lr=1e-2, weight_decay=0.0))
            return w, opt, res

        w, opt, res = w0, adamw_init(w0), zeros_like_residual(w0)
        losses = []
        step = jax.jit(dp_step)
        for i in range(60):
            x = jax.random.normal(jax.random.fold_in(k, i), (64, 16))
            w, opt, res = step(w, opt, res, x)
            losses.append(float(local_loss(w, x)))
        return losses

    exact = make_run(False)
    comp = make_run(True)
    assert comp[-1] < comp[0] * 0.2, comp[::20]
    assert comp[-1] < exact[-1] * 1.5 + 1e-3, (comp[-1], exact[-1])
    print(f"C compressed-DP OK exact={exact[-1]:.4f} compressed={comp[-1]:.4f}")


if __name__ == "__main__":
    check_sharded_train_step()
    check_pipeline_parallel()
    check_compressed_dp()
    print("ALL OK")
