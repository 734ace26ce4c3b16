"""jit'd public wrappers for flash-decode attention (contiguous + paged).

Dispatch policy: the Pallas kernels run compiled on TPU; every other backend
gets the pure-jnp reference, which XLA fuses well — interpret-mode Pallas is
a Python-level emulator meant for kernel correctness work, not serving (see
the retrieval_topk note for measurements of that gap).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_append_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, paged_append_attention_ref,
    paged_decode_attention_ref,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def decode_attention(q, k_cache, v_cache, lengths, *, block_s: int = 256):
    """Fused GQA flash-decode. q [B,H,hd]; caches [B,S,KV,hd]; lengths [B].

    ``block_s`` is a tiling hint; the kernel clamps it to cover S at the
    8-multiple layout constraint, so S < block_s no longer collapses to a
    zero-size sequence grid. The kernel reads kv-head-major caches, so on
    TPU both caches are first copied to ``[B, KV, S, hd]``; no engine step
    calls this (contiguous lanes decode through ``layers.decode_attention``).
    """
    if _on_tpu():
        return decode_attention_pallas(q, k_cache.transpose(0, 2, 1, 3),
                                       v_cache.transpose(0, 2, 1, 3), lengths,
                                       block_s=block_s, interpret=False)
    return decode_attention_ref(q, k_cache, v_cache, lengths)


def paged_decode_attention(q, k_arena, v_arena, page_table, lengths):
    """Paged GQA flash-decode. q [B,H,hd]; arenas [P, KV, page_size, hd];
    page_table [B, n_pages] physical page ids; lengths [B]."""
    if _on_tpu():
        return paged_decode_attention_pallas(q, k_arena, v_arena, page_table,
                                             lengths, interpret=False)
    return paged_decode_attention_ref(q, k_arena, v_arena, page_table,
                                      lengths)


def paged_append_attention(q, k_arena, v_arena, page_table, prefix_len,
                           total_len, *, block_q: int = 128):
    """Chunked paged append attention — the multi-token sibling of
    :func:`paged_decode_attention`, used by prefix-cached suffix prefill.

    q [S, H, hd] (suffix token i at absolute position ``prefix_len + i``);
    arenas [P, KV, page_size, hd]; page_table [n_pages] for ONE request;
    prefix_len / total_len int32 scalars (``total_len`` = prefix + valid
    suffix; padded q rows beyond it return zeros).
    """
    if _on_tpu():
        lens = jnp.stack([jnp.asarray(prefix_len, jnp.int32),
                          jnp.asarray(total_len, jnp.int32)])
        return paged_append_attention_pallas(q, k_arena, v_arena, page_table,
                                             lens, block_q=block_q,
                                             interpret=False)
    return paged_append_attention_ref(q, k_arena, v_arena, page_table,
                                      prefix_len, total_len)


__all__ = ["decode_attention", "decode_attention_ref",
           "paged_decode_attention", "paged_decode_attention_ref",
           "paged_append_attention", "paged_append_attention_ref"]
