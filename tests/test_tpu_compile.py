"""The main-path Pallas kernels compiled by the TPU compiler for a described
v5e chip, at qwen3-0.6b's published widths.

Nothing runs: the compiler refuses here what the chip would refuse (block
shapes off the (8, 128) tiling, too much VMEM), and ``tpu_custom_call`` in
the compiled HLO shows the kernel was lowered through Mosaic rather than
the interpreter.  The kernels are called with ``interpret=False`` directly:
the ``ops`` wrappers pick interpret mode from the CPU backend and would
pass without compiling anything for the chip.

The topology is described inside the fixture only, never at import, so
each test worker collects the same tests and only the one that runs this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.lora import lora_expand_kernel, lora_shrink_kernel
from repro.kernels.paged_attention import paged_attention_kernel

CFG = get_config("qwen3-0.6b")
KV, HD = CFG.n_kv_heads, CFG.resolved_head_dim
GROUP = CFG.n_heads // CFG.n_kv_heads
BS, M = 16, 32                   # 16-token pages, 512-token tables
N_PAGES = 8 * M + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("pages_per_fetch", [1, M])
def test_paged_decode_compiles_at_batch_8(one_chip, pages_per_fetch):
    b = 8
    hlo = _hlo(one_chip,
               lambda q, k, v, t, p, n: paged_attention_kernel(
                   q, k, v, t, p, n, pages_per_fetch=pages_per_fetch,
                   interpret=False),
               ((b, KV, GROUP, HD), jnp.bfloat16),
               ((N_PAGES, BS, KV, HD), jnp.bfloat16),
               ((N_PAGES, BS, KV, HD), jnp.bfloat16),
               ((b, M), jnp.int32), ((b, GROUP), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_paged_prefill_chunk_compiles(one_chip):
    rows = GROUP * BS            # one 16-token chunk, grouped per KV head
    hlo = _hlo(one_chip,
               lambda q, k, v, t, p, n: paged_attention_kernel(
                   q, k, v, t, p, n, pages_per_fetch=M, interpret=False),
               ((1, KV, rows, HD), jnp.bfloat16),
               ((N_PAGES, BS, KV, HD), jnp.bfloat16),
               ((N_PAGES, BS, KV, HD), jnp.bfloat16),
               ((1, M), jnp.int32), ((1, rows), jnp.int32), ((1,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_lora_shrink_compiles(one_chip):
    hlo = _hlo(one_chip,
               lambda x, a, i: lora_shrink_kernel(x, a, i, interpret=False),
               ((8, CFG.d_model), jnp.bfloat16),
               ((4, CFG.d_model, 16), jnp.bfloat16), ((8,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_lora_expand_compiles(one_chip):
    hlo = _hlo(one_chip,
               lambda h, b, i: lora_expand_kernel(h, b, i, jnp.bfloat16,
                                                  block_out=256,
                                                  interpret=False),
               ((8, 16), jnp.float32),
               ((4, 16, CFG.q_dim), jnp.bfloat16), ((8,), jnp.int32))
    assert "tpu_custom_call" in hlo
