"""Compile the serving path's Pallas kernels for a described v5e chip.

Nothing runs: the TPU compiler lowers each kernel at opt-1.3b widths
(d_model 2048, 32 heads of 64, page 16), and the Mamba decode kernels at
Nemotron-H-47B's (256 heads of 64, state 256, 20480 conv channels), for a
chip that is described and not attached, and refuses what the chip would refuse — layouts Mosaic
has no tiling for, blocks that break the (8, 128) rule, scratch that
overflows VMEM.  Interpret-mode tests cannot see any of that.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_attention, paged_prefill, q8_matmul, ssm_step

D_MODEL, HEADS, HEAD_DIM, PAGE = 2048, 32, 64, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [4, 200, 256])
def test_q8_matmul_compiles(one_chip, m, dtype):
    """Decode (M = batch) and prefill (M = batch * prompt, not a multiple
    of the 128-row block) shapes of a streamed 2048 x 1024 shard."""
    k, n = D_MODEL, 1024
    text = _compiled_text(
        functools.partial(q8_matmul.q8_matmul, interpret=False), one_chip,
        ((m, k), dtype), ((k, n), jnp.int8), ((n,), jnp.float32))
    assert "tpu_custom_call" in text


def test_paged_decode_attention_compiles(one_chip):
    b, max_len = 4, 256
    nb = max_len // PAGE
    pages = 1 + b * nb
    text = _compiled_text(
        functools.partial(paged_attention.paged_decode_attention,
                          interpret=False), one_chip,
        ((b, HEADS, HEAD_DIM), jnp.float32),
        ((pages, HEADS, PAGE, HEAD_DIM), jnp.float32),
        ((pages, HEADS, PAGE, HEAD_DIM), jnp.float32),
        ((b, nb), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s", [16, 200])
def test_paged_prefill_attention_compiles(one_chip, s):
    b, max_len = 1, 256
    nb = max_len // PAGE
    pages = 1 + b * nb
    text = _compiled_text(
        functools.partial(paged_prefill.paged_prefill_attention,
                          interpret=False), one_chip,
        ((b, HEADS, s, HEAD_DIM), jnp.float32),
        ((pages, HEADS, PAGE, HEAD_DIM), jnp.float32),
        ((pages, HEADS, PAGE, HEAD_DIM), jnp.float32),
        ((b, nb), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [1, 16])
def test_ssm_state_step_kernels_compile(one_chip, rows):
    """The decode state update at published widths, 16 slots, updating
    its pools in place: the compiled program aliases each pool and adds
    no pool-sized temporary."""
    slots, h, p, n, c = 16, 256, 64, 256, 20480
    for fn, shapes, pool_bytes in (
            (ssm_step.conv_step,
             (((slots, 3, c), jnp.float32), ((rows,), jnp.int32),
              ((rows, c), jnp.float32), ((4, c), jnp.float32),
              ((c,), jnp.float32)), slots * 3 * c * 4),
            (ssm_step.scan_step,
             (((slots, h, p, n), jnp.float32), ((rows,), jnp.int32),
              ((rows, h, p), jnp.float32), ((rows, h), jnp.float32),
              ((rows, h, n), jnp.float32), ((rows, h, n), jnp.float32),
              ((h,), jnp.float32), ((h,), jnp.float32)),
             slots * h * p * n * 4)):
        args = [jax.ShapeDtypeStruct(sh, d, sharding=one_chip)
                for sh, d in shapes]
        compiled = jax.jit(functools.partial(fn, interpret=False),
                           donate_argnums=(0,)).lower(*args).compile()
        mem = compiled.memory_analysis()
        assert "tpu_custom_call" in compiled.as_text()
        assert mem.alias_size_in_bytes == pool_bytes
        assert mem.temp_size_in_bytes < pool_bytes // 8

