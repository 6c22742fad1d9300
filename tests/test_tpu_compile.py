"""The aggregation kernels compile for a TPU v5e at the main path's shapes.

No chip is needed: the TPU compiler compiles for a described (not
attached) v5e topology.  Interpret-mode tests cannot see what only Mosaic
refuses — VMEM exhaustion and vector types the chip cannot load — so each
case compiles the kernel at ResNet-18's largest leaf (512·512·3·3 params)
with the participant counts the server produces: M = K+2 = 22 on the
materializing path and M = 64, the streaming accumulator's batch.  Each
kernel keeps its own name in the compiled program (``pallas_call``'s
``name``), which is how the device trace's op list shows it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dequant_agg import dequant_fedagg, fedagg, float_fedagg

P_LARGEST = 512 * 512 * 3 * 3


@pytest.fixture(scope="module")
def topo():
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("kernel,m,dtype", [
    ("fedagg", 22, jnp.float32),
    ("float_fedagg", 64, jnp.float32),
    ("float_fedagg", 64, jnp.float16),
    ("dequant_fedagg", 64, jnp.int8),
])
def test_aggregation_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                             kernel, m, dtype):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    x, betas = sds((m, P_LARGEST), dtype), sds((m,), jnp.float32)
    if kernel == "dequant_fedagg":
        lowered = dequant_fedagg.lower(x, sds((m,), jnp.float32), betas)
    else:
        fn = fedagg if kernel == "fedagg" else float_fedagg
        lowered = fn.lower(x, betas)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    name = "dequant_fedagg" if kernel == "dequant_fedagg" else "float_fedagg"
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert calls and all(re.search(rf'op_name="[^"]*/{name}/pallas_call"', l)
                         for l in calls), calls
