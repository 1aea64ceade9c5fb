"""GPipe-style pipeline parallelism over a 'pipe' mesh axis (shard_map +
collective_permute), demonstrating the PP capability orthogonally to the
production (data, model) mesh.

Schedule: n_micro microbatches flow through n_stages stages in
n_micro + n_stages - 1 ticks; each tick every stage processes one resident
microbatch and ppermutes its activation to the next stage. Bubble fraction
is (S-1)/(M+S-1), the standard GPipe bound — the test asserts numerical
equality with the sequential composition of the stages.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_forward(stage_params, microbatches, stage_fn, *, mesh, axis_name: str = "pipe"):
    """Run microbatches through staged layers.

    stage_params: pytree with leading dim = n_stages (sharded over 'pipe').
    microbatches: (n_micro, mb, ...) replicated input.
    stage_fn(params_slice, x) -> y, same shape as x.
    Returns (n_micro, mb, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis_name]
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1

    def body(params, mb):
        # params: stage-local slice (leading dim 1); mb: full (replicated)
        my = lax.axis_index(axis_name)
        p_local = jax.tree.map(lambda a: a[0], params)
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            incoming, outputs = carry
            # stage 0 ingests microbatch t (others use the permuted input)
            feed = jnp.where(t < n_micro, 1, 0)
            mb_t = mb[jnp.minimum(t, n_micro - 1)]
            x = jnp.where((my == 0) & (feed == 1), mb_t, incoming)
            y = stage_fn(p_local, x)
            # last stage records its result at slot t - (n_stages - 1)
            out_slot = t - (n_stages - 1)
            write = (my == n_stages - 1) & (out_slot >= 0)
            outputs = lax.cond(
                write,
                lambda o: lax.dynamic_update_index_in_dim(o, y, jnp.maximum(out_slot, 0), 0),
                lambda o: o,
                outputs,
            )
            nxt = lax.ppermute(y, axis_name, fwd_perm)
            return (nxt, outputs), None

        init = (jnp.zeros_like(mb[0]), jnp.zeros_like(mb))
        (_, outputs), _ = lax.scan(tick, init, jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast via masked psum
        # (ppermute is a strict permutation — no one-to-many edges)
        outputs = jnp.where(my == n_stages - 1, outputs, jnp.zeros_like(outputs))
        return lax.psum(outputs, axis_name)

    spec_params = jax.tree.map(lambda _: P(axis_name), stage_params)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec_params, P()), out_specs=P(), check_vma=False
    )
    return fn(stage_params, microbatches)
