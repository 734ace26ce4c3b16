"""Pure-jnp oracles for the flash-decode kernels (contiguous + paged)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def decode_attention_ref(q, k_cache, v_cache, lengths):
    """q [B,H,hd]; k_cache/v_cache [B,S,KV,hd]; lengths [B] -> [B,H,hd].

    Rows with ``length == 0`` return zeros (no valid keys to attend to) —
    the same contract the kernel implements.
    """
    B, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    k = k_cache.astype(jnp.float32)
    v = v_cache.astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k) / np.sqrt(hd)
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return out.reshape(B, H, hd).astype(q.dtype)


def gather_pages(arena, page_table):
    """Logical view of paged KV: arena [P, KV, page_size, hd] and
    page_table [..., n_pages] -> [..., n_pages * page_size, KV, hd], where
    logical position ``t`` comes from ``arena[page_table[..., t //
    page_size], :, t % page_size]``."""
    _, KV, page_size, hd = arena.shape
    g = jnp.swapaxes(arena[page_table], -3, -2)   # [..., n, ps, KV, hd]
    return g.reshape(*page_table.shape[:-1],
                     page_table.shape[-1] * page_size, KV, hd)


def paged_decode_attention_ref(q, k_arena, v_arena, page_table, lengths):
    """Gather-based paged oracle.

    q [B,H,hd]; arenas [P, KV, page_size, hd]; page_table [B, n_pages] of
    physical page ids; lengths [B] -> [B,H,hd]. The gather materializes
    each row's logical [n_pages * page_size, KV, hd] view
    (:func:`gather_pages`) and defers to the contiguous oracle.
    """
    return decode_attention_ref(q, gather_pages(k_arena, page_table),
                                gather_pages(v_arena, page_table), lengths)


def paged_append_attention_ref(q, k_arena, v_arena, page_table, prefix_len,
                               total_len):
    """Gather-based oracle for chunked suffix prefill against paged KV.

    q [S, H, hd] — suffix token i sits at absolute position
    ``prefix_len + i``; arenas [P, KV, page_size, hd]; page_table [n_pages]
    physical page ids for one request; prefix_len/total_len scalars with
    ``total_len = prefix_len + valid_suffix``. The gather materializes the
    request's logical [n_pages * page_size, KV, hd] view (prefix pages
    written by whoever shared them + the suffix this prefill just
    scattered) and runs causal attention: key position <= query position,
    both bounded by ``total_len``. Padded q rows (position >= total_len)
    return zeros.
    """
    S, H, hd = q.shape
    k = gather_pages(k_arena, page_table).astype(jnp.float32)  # [T, KV, hd]
    v = gather_pages(v_arena, page_table).astype(jnp.float32)
    T, KV = k.shape[0], k.shape[1]
    G = H // KV
    qg = q.reshape(S, KV, G, hd).astype(jnp.float32)
    qpos = prefix_len + jnp.arange(S)
    kpos = jnp.arange(T)
    valid = (kpos[None, :] <= qpos[:, None]) & (qpos[:, None] < total_len)
    s = jnp.einsum("skgd,tkd->kgst", qg, k) / np.sqrt(hd)
    s = jnp.where(valid[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[None, None], p, 0.0)
    out = jnp.einsum("kgst,tkd->skgd", p, v)
    return out.reshape(S, H, hd).astype(q.dtype)
