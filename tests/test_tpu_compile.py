"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret-mode parity (``test_kernels.py``) runs the kernel bodies but not
the TPU compiler, which also checks block shapes against the (8, 128)
tiling, VMEM use and Mosaic's lowering rules.  These tests hand the real
compiler the real widths — Qwen2-VL-2B heads (KV 2, group 6) and
Qwen2-VL-7B heads (KV 4, group 7), head_dim 128, 8-token pages, a
1024-patch prefix — with no chip attached.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports every test file.  The persistent
compilation cache is off around these compiles (an entry written without a
chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import kv_quant
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            paged_decode_attention_pallas,
                                            paged_prefill_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.region_score import region_score_pallas

HEADS = {"2b": (2, 6), "7b": (4, 7)}          # (KV heads, query group)
HD, PAGE, SLOTS = 128, 8, 8
N_REGIONS = 1024
#: a slot's pages at the engine's capacity (regions + prompt + 1024-token
#: det answer + γ), and the pool of an 8-slot engine
PAGES_PER_SLOT = -(-(N_REGIONS + 1 + N_REGIONS + 3) // PAGE)
N_PAGES = 1 + SLOTS * PAGES_PER_SLOT + SLOTS * (N_REGIONS // PAGE)
POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8,
               "fp8": kv_quant.FP8_DTYPE}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; the text must hold the Mosaic
    kernel (a ``tpu_custom_call``), not an XLA fallback."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _paged_operands(chip, heads, dtype, q_len):
    kh, group = HEADS[heads]
    pool = (N_PAGES, kh, PAGE, HD)
    ops = [_sds(chip, (SLOTS, kh, q_len * group, HD), jnp.bfloat16),
           _sds(chip, pool, POOL_DTYPES[dtype]),
           _sds(chip, pool, POOL_DTYPES[dtype]),
           _sds(chip, (SLOTS, PAGES_PER_SLOT), jnp.int32),
           _sds(chip, (SLOTS,), jnp.int32)]
    if dtype != "bf16":
        scale = _sds(chip, (N_PAGES, kh, PAGE, 1), jnp.float32)
        ops += [scale, scale]
    return ops


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("dtype", sorted(POOL_DTYPES))
@pytest.mark.parametrize("kernel,q_len", [("decode", 1), ("verify", 4),
                                          ("prefill", 64)])
def test_paged_kernels_compile(one_chip, kernel, q_len, dtype, heads):
    def fn(q, kp, vp, bt, lens, *scales):
        kw = dict(q_len=q_len)
        if scales:
            kw.update(k_scale=scales[0], v_scale=scales[1])
        if kernel == "prefill":
            return paged_prefill_attention_pallas(q, kp, vp, bt, lens,
                                                  q_blk=16, **kw)
        return paged_decode_attention_pallas(q, kp, vp, bt, lens, **kw)
    _compile(fn, *_paged_operands(one_chip, heads, dtype, q_len))


@pytest.mark.parametrize("sq", [N_REGIONS, N_REGIONS + 1])
def test_flash_prefill_compiles(one_chip, sq):
    """The scene prefix (1024 regions) and the dense batch-path prefill
    (regions + prompt token = 1025, a ragged tail block)."""
    kh, group = HEADS["2b"]
    q = _sds(one_chip, (1, kh * group, sq, HD), jnp.bfloat16)
    kv = _sds(one_chip, (1, kh, sq, HD), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True),
             q, kv, kv)


@pytest.mark.parametrize("q_len", [1, 4])
def test_dense_decode_compiles_at_unaligned_capacity(one_chip, q_len):
    """The drafter's dense cache holds regions + prompt + answer + γ = 2052
    slots — no multiple of 128, so the KV tile must not be a divisor."""
    kh, group = HEADS["2b"]
    s = N_REGIONS + 1 + N_REGIONS + 3
    q = _sds(one_chip, (SLOTS, kh, q_len * group, HD), jnp.bfloat16)
    kv = _sds(one_chip, (SLOTS, kh, s, HD), jnp.bfloat16)
    lens = _sds(one_chip, (SLOTS,), jnp.int32)
    _compile(lambda q, k, v, n: decode_attention_pallas(q, k, v, n,
                                                        q_len=q_len),
             q, kv, kv, lens)


def test_region_score_compiles_batched(one_chip):
    """Eq. 2 over a batch of four 1024-region scenes at the 2B width."""
    v = _sds(one_chip, (4, N_REGIONS, 1, 1536), jnp.bfloat16)
    e = _sds(one_chip, (4, 1, 1536), jnp.bfloat16)
    _compile(region_score_pallas, v, e)
