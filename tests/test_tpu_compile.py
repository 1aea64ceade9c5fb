"""Compile-only checks against a described TPU v5e — no chip needed.

The TPU compiler is installed next to JAX, and it compiles for a chip that
is described rather than attached. These tests compile, at the real
widths of the main path (262,144 bins = a 64^3 grid, capacity 32):

* every Pallas kernel the dispatcher registers, and the LM stack's
  segment accumulation, with ``interpret=False`` (``default_backend()`` is
  the CPU here, so the auto-detect would pick the interpreter);
* the dispatcher's TPU rule: no Pallas backend is offered on ``tpu``
  (see ``dispatch._pallas_ok``);
* one XLA window of the uniform scenario at 64^3 x 8 ppc, order 2, whose
  memory must fit a v5e's HBM.

Nothing runs: a pass says the program compiles and fits, not that it is
correct or fast (chip_smoke.py checks that on the chip).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.shape_functions import support, unified_support
from repro.kernels import dispatch

N_BINS, CAP = 262_144, 32  # uniform 64^3 at 8 ppc: capacity 32
V5E_HBM_BYTES = 15.75 * 1024**3  # what the compiler lets a program use

#: (op, backend) of every Pallas kernel the dispatcher registers
PALLAS = [
    (op, name)
    for op in dispatch.ops()
    for name in sorted(dispatch.backends_for(op))
    if name != "xla"
]

_compiled: dict = {}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _kernel(op: str, backend: str, order: int, n_bins: int):
    """(fn, operand shapes) of one Pallas kernel at a dispatch key, compiled
    (never interpreted)."""
    from repro.kernels.deposition.kernel import (
        bin_outer_product_pallas,
        fused_deposition_pallas,
        fused_deposition_reduced_pallas,
    )
    from repro.kernels.gather.kernel import bin_gather_pallas, fused_gather_pallas

    t, _ = unified_support(order)
    m, _ = support(order, True)
    n = support(order, False)[0] ** 2
    slab = (n_bins, CAP, 3)
    if (op, backend) == ("deposit_fused", "pallas"):
        return lambda d, v: fused_deposition_pallas(d, v, order=order, interpret=False), [slab, slab]
    if (op, backend) == ("deposit_fused", "pallas_reduced"):
        nz = 64
        grid = (n_bins // (nz * nz), nz, nz)
        return (
            lambda d, v: fused_deposition_reduced_pallas(
                d, v, order=order, grid_shape=grid, guard=order, interpret=False
            ),
            [slab, slab],
        )
    if (op, backend) == ("gather_fused", "pallas"):
        return (
            lambda d, g: fused_gather_pallas(d, g, order=order, interpret=False),
            [slab, (n_bins, 6, t, t * t)],
        )
    if (op, backend) == ("deposit_unfused", "pallas"):
        return (
            lambda a, b: bin_outer_product_pallas(a, b, interpret=False),
            [(n_bins, CAP, m), (n_bins, CAP, n)],
        )
    if (op, backend) == ("bin_gather", "pallas"):
        return (
            lambda wx, byz, g: bin_gather_pallas(wx, byz, g, interpret=False),
            [(n_bins, CAP, m), (n_bins, CAP, n), (n_bins, m, n)],
        )
    raise AssertionError(f"no compile case for {op}/{backend}: add one")


def _compile(one_chip, fn, shapes):
    """Compile ``fn`` for the described chip: None, or the compiler's error."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in shapes]
    try:
        jax.jit(fn).lower(*args).compile()
    except Exception as e:  # the compiler's refusal IS the result here
        return e
    return None


def _compile_kernel(one_chip, op, backend, order, n_bins):
    """Compile once per case: None, or the compiler's error."""
    case = (op, backend, order, n_bins)
    if case not in _compiled:
        _compiled[case] = _compile(one_chip, *_kernel(op, backend, order, n_bins))
    return _compiled[case]


def _tpu_key(op, order, n_bins):
    return dispatch.DispatchKey(
        op=op, order=order, grid_shape=None, capacity=CAP, n_bins=n_bins,
        dtype="float32", platform="tpu", interpret=False,
    )


@pytest.mark.parametrize("order", [1, 2, 3])
@pytest.mark.parametrize("op,backend", PALLAS)
def test_pallas_kernel_compiles_for_v5e(one_chip, op, backend, order):
    """Mosaic lowers and compiles every registered Pallas kernel at the
    main path's width — except the epilogue-fused deposition, whose lane
    reshape Mosaic rejects."""
    got = _compile_kernel(one_chip, op, backend, order, N_BINS)
    if backend == "pallas_reduced":
        assert isinstance(got, Exception) and "unsupported shape cast" in str(got), got
    else:
        assert got is None, got


def test_segment_accumulate_compiles_for_v5e(one_chip):
    """The LM stack's segment accumulation compiles at an embedding
    gradient's width: 32,768 bins, capacity 16, 1,024 features."""
    from repro.kernels.scatter_matrix.kernel import segment_accumulate_pallas

    v, cap, d = 32_768, 16, 1024
    got = _compile(
        one_chip, lambda w, u: segment_accumulate_pallas(w, u, interpret=False),
        [(v, cap), (v, cap, d)],
    )
    assert got is None, got


@pytest.mark.parametrize("op,backend", PALLAS)
def test_tpu_offers_no_pallas_backend(op, backend):
    """On tpu every "auto" resolves to xla: no Pallas kernel is offered
    there until one runs on the chip within HBM (dispatch._pallas_ok)."""
    for n_bins in (N_BINS, 4096):
        assert not dispatch.backends_for(op)[backend].is_available(_tpu_key(op, 2, n_bins))


def test_xla_uniform_window_fits_v5e(one_chip):
    """The main path's compiled window — uniform 64^3 x 8 ppc, order 2,
    XLA backend, health sentinel on, 8 steps — fits one v5e's HBM."""
    from repro.api import scenario
    from repro.api.facade import build_fields, build_particles, pic_config
    from repro.core import build_bins, cell_index, policy_init, sort_permutation
    from repro.core.health import HealthConfig
    from repro.distributed.fault import no_fault_vec
    from repro.grad.permutations import permute_tree
    from repro.pic import simulation as sim

    spec = scenario("uniform", grid=(64, 64, 64), ppc=2, order=2, backend="xla")
    cfg = pic_config(spec)
    assert cfg.grid.n_cells * spec.plasma.ppc == 2_097_152 and cfg.capacity == CAP

    def initial_state():  # init_state without its host-side overflow check
        particles = build_particles(spec)
        cells = cell_index(particles.pos, cfg.grid.shape)
        particles = permute_tree(particles, sort_permutation(cells, particles.alive))
        cells = cell_index(particles.pos, cfg.grid.shape)
        layout, _ = build_bins(
            cells, particles.alive, n_cells=cfg.grid.n_cells, capacity=cfg.capacity
        )
        return sim.PICState(
            fields=build_fields(spec), particles=particles, layout=layout,
            step=jnp.int32(0), slab=sim._state_slab(particles, layout, cfg),
        )

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree
        )

    window = jax.jit(
        sim._pic_run_window_impl, static_argnames=sim._WINDOW_STATICS, donate_argnums=(0, 1)
    )
    compiled = window.lower(
        on_chip(jax.eval_shape(initial_state)),
        on_chip(jax.eval_shape(policy_init)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        on_chip(jax.eval_shape(no_fault_vec)),
        config=cfg, policy=spec.sort.policy, n_steps=8, with_energies=True,
        health=HealthConfig(enable=True), with_fault=False,
    ).compile()
    ma = compiled.memory_analysis()
    need = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    )
    assert need <= V5E_HBM_BYTES, need
    assert "tpu_custom_call" not in compiled.as_text()  # the XLA route: no Pallas
