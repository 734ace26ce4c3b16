"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see the TPU compiler's
layout rules: the last two dims of a block must match the array's or be
(8, 128) multiples, and a rank-1 block must cover its array or be a
multiple of 128. These cases compile each kernel at real serving widths
against a described, not attached, ``v5e:2x2`` topology, so a kernel the
chip would refuse fails here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. All compiles stay in this one file for the same reason.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_append_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.rbf.kernel import rbf_matrix_pallas

# (query heads, kv heads, head dim) per chip
WIDTHS = {
    "qwen2-0.5b": (14, 2, 64),        # whole model on one chip
    "qwen2-72b-tp8": (8, 1, 128),     # one chip's share of 64/8 heads, TP 8
}
MAX_SEQ, BATCH, SUFFIX = 4096, 8, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any reason it cannot be
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("page_size", [16, 128])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_compiles(spec, width, page_size):
    H, KV, hd = WIDTHS[width]
    n_pages = MAX_SEQ // page_size
    arena = spec((BATCH * n_pages + 1, KV, page_size, hd))
    text = _compile_kernel(
        functools.partial(paged_decode_attention_pallas, interpret=False),
        spec((BATCH, H, hd)), arena, arena,
        spec((BATCH, n_pages), jnp.int32), spec((BATCH,), jnp.int32))
    # the name the benchmark's roofline readers match in the device trace
    assert "paged_decode_attention" in text


@pytest.mark.parametrize("page_size", [16, 128])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_append_compiles(spec, width, page_size):
    H, KV, hd = WIDTHS[width]
    n_pages = MAX_SEQ // page_size
    arena = spec((BATCH * n_pages + 1, KV, page_size, hd))
    text = _compile_kernel(
        functools.partial(paged_append_attention_pallas, interpret=False),
        spec((SUFFIX, H, hd)), arena, arena, spec((n_pages,), jnp.int32),
        spec((2,), jnp.int32))
    assert "paged_append_attention" in text


@pytest.mark.parametrize("width,n_pages", [("qwen2-0.5b", 256),
                                           ("qwen2-72b-tp8", 1024)])
def test_paged_append_compiles_at_served_size(spec, width, n_pages):
    """A served chunk (2,048 tokens, 16-token pages) against each tier's
    page table: the multi-page KV blocks' working set must fit the
    kernel's VMEM at both widths."""
    H, KV, hd = WIDTHS[width]
    arena = spec((n_pages + 1, KV, 16, hd))
    text = _compile_kernel(
        functools.partial(paged_append_attention_pallas, interpret=False),
        spec((2048, H, hd)), arena, arena, spec((n_pages,), jnp.int32),
        spec((2,), jnp.int32))
    assert "paged_append_attention" in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_contiguous_decode_compiles(spec, width):
    H, KV, hd = WIDTHS[width]
    cache = spec((BATCH, KV, MAX_SEQ, hd))
    _compile_kernel(
        functools.partial(decode_attention_pallas, interpret=False),
        spec((BATCH, H, hd)), cache, cache, spec((BATCH,), jnp.int32))


def test_rbf_matrix_compiles(spec):
    f32 = jnp.float32
    _compile_kernel(functools.partial(rbf_matrix_pallas, interpret=False),
                    spec((300, 7), f32), spec((200, 7), f32), spec((), f32),
                    spec((), f32))
