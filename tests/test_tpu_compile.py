"""The Pallas kernels of the main path compile for the chip.

Interpret mode (every other kernel test) cannot see what the TPU's
compiler refuses: a block shape off the (8, 128) tiling, an unsupported
vector type, too much VMEM.  These tests ask that compiler directly — it
is installed with jaxlib and compiles for a chip that is *described*, not
attached — at the shapes ``chip_smoke.py`` runs on the v5e, with
``interpret=False``.  Nothing executes, so they say nothing about results
or times; ``chip_smoke.py`` checks the numerics on the chip.

All of them live in this one file and describe the topology inside a
fixture: only one process at a time may load the TPU library, and under
pytest-xdist only the worker that is handed this file does.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.parallel.flash import flash_attention
from horovod_tpu.serve.paged_attention import (SCALE_DTYPE,
                                               paged_decode_attention,
                                               paged_prefill_attention)

# GPT-2 small serving geometry (12 heads of 64) and the BERT/GPT-2 medium
# training geometry (16 heads of 64), as chip_smoke.py's kernel phase.
FLASH_B, FLASH_H, FLASH_D = 8, 16, 64
NB, BT, H, DH = 256, 16, 12, 64
SERVE_B, SERVE_MB, PREFILL_C = 8, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no log files in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel_compiles(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("seq", [128, 1024])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          backward, seq, causal):
    qkv = jax.ShapeDtypeStruct((FLASH_B, seq, FLASH_H, FLASH_D),
                               jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=False)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    _assert_kernel_compiles(fwd_bwd if backward else fwd, qkv, qkv, qkv)


@pytest.mark.parametrize("kv", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_paged_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                          phase, kv):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q_dtype = jnp.bfloat16 if kv == "bf16" else jnp.float32
    pool_dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16,
                  "int8": jnp.int8}[kv]
    q_shape = ((SERVE_B, H, DH) if phase == "decode"
               else (SERVE_B, PREFILL_C, H, DH))
    attend = (paged_decode_attention if phase == "decode"
              else paged_prefill_attention)
    pool = sds((NB, BT, H, DH), pool_dtype)
    scales = (sds((NB, BT, H), SCALE_DTYPE),) * 2 if kv == "int8" else ()

    def fn(q, k, v, tables, positions, *scale_rows):
        k_scale, v_scale = scale_rows or (None, None)
        return attend(q, k, v, tables, positions, k_scale=k_scale,
                      v_scale=v_scale, interpret=False)

    _assert_kernel_compiles(
        fn, sds(q_shape, q_dtype), pool, pool,
        sds((SERVE_B, SERVE_MB), jnp.int32), sds((SERVE_B,), jnp.int32),
        *scales)
