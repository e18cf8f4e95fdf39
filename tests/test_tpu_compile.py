"""Compile rehearsals for one chip of a described TPU v5e: the delta kernels
at the codec's size for mamba2-370m, and the mamba2-370m train step at
published widths. The TPU compiler runs here without a chip; nothing
executes, but whatever the chip's compiler would refuse (unaligned blocks,
too much VMEM, a program larger than HBM) fails here."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.checkpoint.delta import padded_blocks
from repro.configs import get_config
from repro.kernels import delta_encode as de
from repro.models import param_count, param_descs
from repro.train import init_train_state, train_step_fn

CFG = get_config("mamba2_370m")
BATCH, SEQ = 4, 1024  # chip_smoke.py's training batch
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: an entry compiled for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", ["encode", "decode"])
def test_delta_kernel_compiles_at_mamba2_370m_size(one_chip, kernel, dtype):
    nb = padded_blocks(param_count(param_descs(CFG)))
    blocks = _on(one_chip, jax.ShapeDtypeStruct((nb, 1024), dtype))
    if kernel == "encode":
        lowered = jax.jit(de.delta_encode).lower(blocks, blocks)
    else:
        codes = _on(one_chip, jax.ShapeDtypeStruct((nb, 1024), jnp.int8))
        scales = _on(one_chip, jax.ShapeDtypeStruct((nb,), jnp.float32))
        lowered = jax.jit(de.delta_decode, static_argnames="dtype").lower(
            codes, scales, blocks, dtype=jnp.float32
        )
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_mamba2_370m_train_step_fits_one_v5e(one_chip):
    state = jax.eval_shape(lambda: init_train_state(CFG))
    batch = _on(one_chip, {"tokens": jax.ShapeDtypeStruct((BATCH, SEQ + 1), jnp.int32)})
    mem = train_step_fn(CFG).lower(*_on(one_chip, state), batch).compile().memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state))
    # params and Adam state are donated: their outputs reuse the inputs
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes, (mem.alias_size_in_bytes, state_bytes)
    unaliased_out = mem.output_size_in_bytes - mem.alias_size_in_bytes
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + unaliased_out
    assert total < HBM_BYTES, mem
