"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib, so these tests lower and
compile at the paper's size for a `v5e:2x2` topology without a chip:
they catch what the chip's compiler would refuse (tiling, VMEM, device
memory) and show that the Pallas backends compile the Mosaic kernel
(`tpu_custom_call`) rather than the interpreter. Nothing runs, so they
say nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports every test file.
"""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.common import engine_cfg
from repro.core import engine as eng
from repro.kernels.proximity import ops

N = 10_000  # the paper's SE count
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # conftest turns the persistent cache on; an entry compiled for
        # a described chip cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile_window(cfg, sharding):
    """The engine's jitted scan (one step) compiled for the chip."""
    state = jax.eval_shape(lambda k: eng._init_engine(k, cfg),
                           jax.random.key(0))
    mf = jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)
    return eng._compiled_window(cfg, 1).lower(
        _shapes(state, sharding), mf).compile()


@pytest.mark.parametrize("kernel", ["dense", "grid"])
def test_proximity_kernel_compiles_to_mosaic(one_chip, kernel):
    abm = engine_cfg("paper").abm
    args = _shapes((jax.ShapeDtypeStruct((N, 2), jnp.float32),
                    jax.ShapeDtypeStruct((N,), jnp.int32),
                    jax.ShapeDtypeStruct((N,), jnp.bool_)), one_chip)
    if kernel == "dense":
        fn = lambda p, l, s: ops.proximity_lp_counts(  # noqa: E731
            p, l, s, abm.n_lp, abm.area, abm.interaction_range)
    else:
        spec = abm.grid_spec()
        assert spec is not None
        fn = lambda p, l, s: ops.proximity_lp_counts_grid(  # noqa: E731
            p, l, s, abm.n_lp, abm.area, abm.interaction_range, spec)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_paper_pallas_step_runs_the_mosaic_kernel(one_chip):
    """The engine's `pallas` backend at paper size must lower to the
    compiled kernel on a TPU, never to the interpreter's emulation."""
    compiled = _compile_window(engine_cfg("paper", backend="pallas"),
                               one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_paper_grid_step_fits_one_chip(one_chip):
    ma = _compile_window(engine_cfg("paper"), one_chip).memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES
