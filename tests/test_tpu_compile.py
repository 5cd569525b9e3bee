"""Compile the Pallas kernels at real widths for a described TPU v5e.

Nothing runs: the TPU compiler, installed here without a chip, compiles each
kernel for one chip of a described v5e:2x2 with ``interpret=False`` and must
emit the Mosaic kernel (``tpu_custom_call``).  The topology is described only
inside the ``topo`` fixture, never while a module is imported, so pytest
workers that do not run this file never load the TPU library.  The
persistent compilation cache is off around these compiles: an entry written
for a described chip cannot be read back without one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler or topology here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_swa_attention_compiles_for_v5e(one_chip):
    x = _shape((32, 4096, 128), jnp.bfloat16, one_chip)
    text = ops.swa_attention.lower(x, x, x, window=1024,
                                   interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles_for_v5e(one_chip):
    x = _shape((8192, 2048), jnp.bfloat16, one_chip)
    w = _shape((2048,), jnp.float32, one_chip)
    text = ops.rmsnorm.lower(x, w, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


def test_fused_sgd_update_compiles_for_resnet110(one_chip):
    """Over every ResNet-110 parameter: 1,727,962 is no multiple of the
    block, so the kernel's padding path is compiled too."""
    from repro.configs.resnet110 import CONFIG
    from repro.models.resnet import ResNetModel

    shapes = jax.eval_shape(ResNetModel(CONFIG).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == 1_727_962 and n % 65536 != 0
    flat = _shape((n,), jnp.float32, one_chip)
    lr = _shape((), jnp.float32, one_chip)
    text = ops.fused_sgd_update.lower(flat, flat, flat, lr, block=65536,
                                      interpret=False).compile().as_text()
    assert "tpu_custom_call" in text
