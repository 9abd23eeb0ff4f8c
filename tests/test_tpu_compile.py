"""Both Pallas kernels compile for a described TPU v5e at real widths.

Interpret mode runs the kernel body in Python and never meets the TPU's
tiling rules or its VMEM limit; the TPU compiler does. libtpu compiles for a
chip that is described and not attached, so these tests guard the chip path
without one. The topology is described inside a fixture, never at import:
only one process may load libtpu at a time, and every xdist worker imports
this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gemv_w4a8.ops import gemv_w4a8
from repro.kernels.swiftkv_decode.ops import swiftkv_decode


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# B, G (query heads per KV head) and S as the chip smoke serves them; Hkv = 8
# is h2o-danube-1.8b's and qwen3-8b's, D = 80 and 128 their head dims
B, HKV, G, S = 4, 8, 4, 2048


@pytest.mark.parametrize("kind", ["linear", "ring", "int8"])
@pytest.mark.parametrize("d", [80, 128])
def test_swiftkv_decode_compiles_for_v5e(one_chip, d, kind):
    kv_dtype = jnp.int8 if kind == "int8" else jnp.bfloat16
    args = [_spec(one_chip, (B, HKV * G, d), jnp.bfloat16),
            _spec(one_chip, (B, S, HKV, d), kv_dtype),
            _spec(one_chip, (B, S, HKV, d), kv_dtype),
            _spec(one_chip, (B,), jnp.int32)]
    kw = {}
    if kind == "ring":
        kw = {"ring": True, "window": 1024}
    if kind == "int8":
        kw = {"k_scale": _spec(one_chip, (B, HKV, S), jnp.bfloat16),
              "v_scale": _spec(one_chip, (B, HKV, S), jnp.bfloat16)}
    _assert_kernel(swiftkv_decode.lower(*args, **kw).compile())


def test_swiftkv_decode_mha_compiles_for_v5e(one_chip):
    """32 KV heads of 128 (llama2-7b's MHA): a 512-row tile of every head
    would not fit VMEM double-buffered, so ops caps block_k at 256."""
    args = [_spec(one_chip, (B, 32, 128), jnp.bfloat16),
            _spec(one_chip, (B, S, 32, 128), jnp.bfloat16),
            _spec(one_chip, (B, S, 32, 128), jnp.bfloat16),
            _spec(one_chip, (B,), jnp.int32)]
    _assert_kernel(swiftkv_decode.lower(*args).compile())


@pytest.mark.parametrize("k,n", [
    (2560, 6912),      # h2o-danube-1.8b's up/gate projection
    (768, 2560),       # K = 1.5 blocks: the wrapper pads K to block_k
])
def test_gemv_w4a8_compiles_for_v5e(one_chip, k, n):
    args = [_spec(one_chip, (8, k), jnp.bfloat16),
            _spec(one_chip, (k, n // 2), jnp.uint8),
            _spec(one_chip, (k // 128, n), jnp.float32)]
    _assert_kernel(gemv_w4a8.lower(*args).compile())
