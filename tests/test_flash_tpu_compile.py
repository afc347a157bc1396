"""The flash kernels compiled for a described (not attached) TPU v5e, at
real sizes: Mosaic's layout rules and its 16 MB scoped-VMEM limit are what
the interpreter cannot check and what ``flash_tile_plan``'s own estimate
has to stay under. Nothing runs; a pass says the chip's compiler takes the
kernels, not that they are right or fast (tests/test_attention_ops.py,
chip_smoke.py).

The topology is described inside a fixture and only in this file: one
process loads the TPU library, and keeps it."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_tensorflow_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape,dtype,causal,padded", [
    ((8, 16, 1024, 64), jnp.bfloat16, True, False),   # gpt2m_train_1k
    ((8, 12, 384, 64), jnp.bfloat16, False, True),    # BERT padded to 384
    ((8, 12, 640, 64), jnp.bfloat16, False, True),
    ((2, 16, 2048, 128), jnp.bfloat16, True, False),
    ((1, 8, 8192, 128), jnp.bfloat16, True, False),   # spans do not fit
    ((1, 8, 4096, 128), jnp.float32, True, True),
], ids=lambda v: str(getattr(v, "__name__", v)).replace(" ", ""))
def test_flash_forward_and_backward_compile_for_v5e(
        one_chip, shape, dtype, causal, padded):
    B, _, S, _ = shape
    qkv = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((B, S), jnp.bool_, sharding=one_chip)

    def step(q, k, v, kv_mask):
        def loss(q, k, v):
            out = flash_attention(
                q, k, v, causal=causal, interpret=False,
                kv_mask=kv_mask if padded else None)
            return out.astype(jnp.float32).sum()
        return jax.grad(loss, (0, 1, 2))(q, k, v)

    text = jax.jit(step).lower(qkv, qkv, qkv, mask).compile().as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        assert kernel in text
