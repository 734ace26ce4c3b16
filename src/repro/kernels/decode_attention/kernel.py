"""Pallas TPU kernels: fused GQA flash attention against a KV cache —
contiguous decode, paged decode, and paged *append* (chunked suffix
prefill).

One online-softmax accumulation over sequence blocks serves three callers:

* contiguous decode — ``k_cache/v_cache [B, KV, S, hd]``: the grid
  iterates (batch, kv_head, seq_block) and each program consumes one
  ``[block_s, hd]`` cache tile.
* paged decode — ``k_arena/v_arena [num_pages, KV, page_size, hd]`` plus a
  per-row ``page_table [B, n_pages]`` of physical page ids: the grid's
  seq-block axis indexes *through the page table* (one program per logical
  page) using Pallas scalar prefetch, so the same online-softmax
  accumulators run over a scattered arena without materializing a
  contiguous copy.
* paged append — the multi-token sibling of paged decode, used by
  prefix-cached suffix prefill: q is a ``[block_q, H, hd]`` chunk of new
  tokens at absolute positions ``prefix_len + i``, and the grid's seq axis
  walks KV blocks of many pages, each page chasing the (scalar-prefetched)
  page table over *prefix + suffix* pages, up to the tile's causal
  frontier.
  The causal mask lives entirely inside the q tile's position arithmetic:
  key position <= query position admits every shared-prefix key and the
  already-written part of the suffix, exactly like a causal prefill over
  the logically reassembled cache.

TPU adaptation (vs a CUDA warp-per-row decode kernel): each program instance
processes a whole ``[BS, hd]`` cache tile from VMEM against the query tile
(``[G, hd]`` for decode, ``[block_q * G, hd]`` for append) on the MXU, with
running max / sum-exp / weighted-value accumulators in VMEM scratch. A
tile spans the whole head dim (64 for qwen2-0.5b, 128 for qwen2-72b) and BS
is a multiple of 8 for the VPU/MXU layout. Masking uses per-row valid
lengths/positions; probabilities AND values are zeroed outside them, so
out-of-bounds tile padding (NaN in interpret mode, garbage on TPU) and
fully-masked rows (defined to return zeros) never reach the accumulators.

Two layout rules of the TPU compiler shape every operand here:

* the last two dims of a block must equal the array's or be (8, 128)
  multiples. A k/v block is therefore ``[.., page_size/block_s, hd]`` with
  the kv-head axis squeezed OUTSIDE those two dims — the arena is laid out
  ``[P, KV, page_size, hd]`` and the contiguous cache ``[B, KV, S, hd]``.
  Squeezing KV out of ``[.., KV, hd]`` would leave a block of 1 row where
  the array has KV (refused for KV > 1).
* a rank-1 block must cover the array or be a 128-multiple, so per-row
  lengths cannot ride in as ``(1,)`` VMEM blocks. Lengths, like page
  tables, are scalar-prefetch operands (SMEM) read with the program id.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _softmax_accumulate(q, k, v, valid, m_ref, l_ref, acc_ref, *,
                        scale: float):
    """One online-softmax block step, shared by decode and append.

    q [R, hd], k/v [BS, hd] (f32), valid [R, BS] boolean keep-mask with the
    caller's causal/length semantics baked in; running max / sum-exp /
    weighted-value accumulators in VMEM scratch ([R, 1], [R, 1], [R, hd]).
    The caller must zero v rows that can hold undefined data BEFORE calling
    (0 * NaN would poison the p @ v product)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]                                   # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # masked probabilities are forced to exact 0 — a fully-masked tile would
    # otherwise contribute exp(NEG_INF - NEG_INF) = 1 per position (NEG_INF
    # is a finite sentinel) and a fully-masked row would average garbage
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)         # [R, BS]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _flash_decode_body(len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, block_s: int, scale: float):
    """Decode online-softmax block step; grid axis 2 walks sequence tiles.

    q_ref:   [G, hd]      (this batch row, this kv head's query group)
    k_ref:   [block_s, hd]
    v_ref:   [block_s, hd]
    len_ref: [B]          (SMEM: valid cache length of every row)
    o_ref:   [G, hd]
    scratch: m_ref [G, 1], l_ref [G, 1], acc_ref [G, hd]  (f32)

    Tile rows hold *logical* positions ``s_idx * block_s + i`` regardless of
    layout: contiguous callers map grid index -> cache offset directly,
    paged callers map it through the page table in their BlockSpecs, so the
    masking below is layout-agnostic.
    """
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)                    # [G, hd]
    k = k_ref[...].astype(jnp.float32)                    # [BS, hd]
    v = v_ref[...].astype(jnp.float32)

    tile_start = s_idx * block_s
    length = len_ref[pl.program_id(0)]
    # zero cache-value rows beyond the valid length BEFORE they can meet the
    # accumulators: tile padding past the array end is undefined (NaN in
    # interpret mode) and 0 * NaN would poison the p @ v product
    pos_col = tile_start + jax.lax.broadcasted_iota(jnp.int32, (block_s, 1), 0)
    v = jnp.where(pos_col < length, v, 0.0)

    pos = tile_start + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], block_s), 1)
    _softmax_accumulate(q, k, v, pos < length, m_ref, l_ref, acc_ref,
                        scale=scale)

    @pl.when(s_idx == n_s - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def _paged_decode_attn_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                              m_ref, l_ref, acc_ref, *, page_size: int,
                              scale: float):
    """Paged layout. Grid: (B, KV, n_pages); ``pt_ref`` is the scalar-
    prefetched page table — the k/v BlockSpecs already used it to DMA the
    physical page for this (row, logical page) program, so the body only
    needs the logical position ``page_idx * page_size`` for masking."""
    _flash_decode_body(len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, block_s=page_size, scale=scale)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, lengths, *,
                            block_s: int = 256, interpret: bool = True):
    """q [B,H,hd]; k_cache/v_cache [B,KV,S,hd] (kv-head-major); lengths [B]
    -> [B,H,hd].

    ``block_s`` is clamped to cover S at the 8-multiple VPU/MXU layout
    constraint; a cache shorter than the block therefore runs a single
    (padded, masked) program instead of a zero-size grid. The caches come
    kv-head-major so each tile's last two dims are ``(block_s, hd)``; the
    model's contiguous lanes are ``[B, S, KV, hd]`` and decode through the
    jnp path, so this kernel serves no engine step.
    """
    B, H, hd = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    block_s = max(8, min(_round_up(block_s, 8), _round_up(S, 8)))
    scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(B, KV, G, hd)
    lengths = lengths.astype(jnp.int32)

    kernel = functools.partial(_flash_decode_body, block_s=block_s,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # the lengths
        grid=(B, KV, -(-S // block_s)),  # ceil: ragged tail tile is masked
        in_specs=[
            pl.BlockSpec((None, None, G, hd),
                         lambda b, h, s, lens: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_s, hd),
                         lambda b, h, s, lens: (b, h, s, 0)),
            pl.BlockSpec((None, None, block_s, hd),
                         lambda b, h, s, lens: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, hd),
                               lambda b, h, s, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),       # running max
            pltpu.VMEM((G, 1), jnp.float32),       # running sum-exp
            pltpu.VMEM((G, hd), jnp.float32),      # running weighted values
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
    return out.reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_arena, v_arena, page_table, lengths, *,
                                  interpret: bool = True):
    """Paged flash-decode: q [B,H,hd]; arenas [P, KV, page_size, hd];
    page_table [B, n_pages] int32 physical page ids; lengths [B] -> [B,H,hd].

    One program per (row, kv_head, logical page). The page table and the
    lengths ride in as scalar-prefetch operands so the k/v BlockSpec index
    maps can chase the table: program (b, h, i) DMAs head h of physical page
    ``page_table[b, i]``. Entries past a row's valid length may point
    anywhere (allocators pad with a trash page) — they are masked by
    ``lengths`` exactly like the contiguous tail.
    """
    B, H, hd = q.shape
    P, KV, page_size, _ = k_arena.shape
    n_pages = page_table.shape[1]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(B, KV, G, hd)
    lengths = lengths.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)

    kernel = functools.partial(_paged_decode_attn_kernel,
                               page_size=page_size, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # page table, lengths
        grid=(B, KV, n_pages),
        in_specs=[
            pl.BlockSpec((None, None, G, hd),
                         lambda b, h, i, pt, lens: (b, h, 0, 0)),
            pl.BlockSpec((None, None, page_size, hd),
                         lambda b, h, i, pt, lens: (pt[b, i], h, 0, 0)),
            pl.BlockSpec((None, None, page_size, hd),
                         lambda b, h, i, pt, lens: (pt[b, i], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G, hd),
                               lambda b, h, i, pt, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(page_table, lengths, qg, k_arena, v_arena)
    return out.reshape(B, H, hd)


# KV tokens one append grid step walks: 32 pages at page size 16, 4 at 128
APPEND_BLOCK_TOKENS = 512


def _append_tiling(S: int, n_pages: int, page_size: int, block_q: int):
    """(block_q, pages_per_block, n_blocks) of the append kernel's grid.

    ``block_q`` is clamped to divide S at a multiple of 8; a KV block is
    ``APPEND_BLOCK_TOKENS`` worth of pages, at least one and at most the
    whole page table."""
    block_q = min(block_q, S)
    while S % block_q:
        block_q -= 8
    ppb = max(1, min(n_pages, APPEND_BLOCK_TOKENS // page_size))
    return block_q, ppb, -(-n_pages // ppb)


def _tile_kv_end(q_first, total, block_q: int, xp):
    """One past the last key any valid query of a tile can attend: the tile
    starts at absolute position ``q_first``, its last valid query sits at
    ``min(q_first + block_q, total) - 1``; a tile wholly at or past
    ``total`` (chunk padding) attends nothing. ``xp`` is ``jnp`` for the
    kernel and ``numpy`` for the host's count, so both walk by one rule."""
    return xp.where(q_first < total, xp.minimum(q_first + block_q, total), 0)


def append_walk(prefix_len: int, total_len: int, chunk: int, n_pages: int,
                page_size: int, *, block_q: int = 128):
    """(walked, grid): the (query tile, KV block) pairs of one KV head that
    :func:`paged_append_attention_pallas` computes for a ``chunk``-token
    (padded) suffix at ``prefix_len`` with ``total_len`` valid positions,
    against the pairs in its static grid. Host arithmetic only."""
    block_q, ppb, n_blocks = _append_tiling(chunk, n_pages, page_size,
                                            block_q)
    bt = ppb * page_size
    n_qc = chunk // block_q
    walked = sum(-(-int(_tile_kv_end(prefix_len + c * block_q, total_len,
                                     block_q, np)) // bt)
                 for c in range(n_qc))
    return walked, n_qc * n_blocks


def _append_walk_steps(prefix, total, n_qc: int, n_blocks: int,
                       block_q: int, pages_per_block: int, page_size: int):
    """What each grid step (c, j) of the append kernel holds: the query
    tile, ``[n_qc]``, and the KV block's first page-table index,
    ``[n_qc * n_blocks]``. A live step holds its own; a step past its
    tile's frontier holds the tile's last live block; a tile of padding
    holds the last live tile and its last block. A skipped step thus
    repeats the step before it, and the pipeline, which copies only blocks
    that change, issues no DMA for it."""
    bt = pages_per_block * page_size
    last = jnp.maximum((total - 1 - prefix) // block_q, 0)
    tile = jnp.minimum(jnp.arange(n_qc, dtype=jnp.int32), last)
    n_live = (_tile_kv_end(prefix + tile * block_q, total, block_q, jnp)
              + bt - 1) // bt
    blk = jnp.minimum(jnp.arange(n_blocks, dtype=jnp.int32)[None, :],
                      jnp.maximum(n_live - 1, 0)[:, None])
    return tile, (blk * pages_per_block).reshape(-1)


def _paged_append_attn_kernel(pt_ref, len_ref, tile_ref, base_ref, q_ref,
                              *refs, pages_per_block: int, page_size: int,
                              block_q: int, group: int, scale: float):
    """Paged append (chunked suffix prefill). Grid: (KV, n_q_chunks,
    n_blocks) with the KV-block axis innermost so the accumulators carry
    across the whole logical sequence. A KV block is ``pages_per_block``
    logical pages: k and v each arrive as that many ``[page_size, hd]``
    page operands, whose BlockSpecs chased the scalar-prefetched page
    table (the pipeline loads block i + 1 while block i computes), and are
    stacked here into one ``[pages_per_block * page_size, hd]`` block.
    ``tile_ref``/``base_ref`` (SMEM) are :func:`_append_walk_steps`: only
    the index maps read them. Pages come through BlockSpecs rather than
    DMAs issued from an HBM (``pl.ANY``) arena because the TPU compiler
    pads a 64-wide minor dim to 128 lanes in HBM and refuses a DMA slice
    of it, which rules out qwen2-0.5b's head dim of 64.

    The walk stops at the tile's causal frontier: blocks whose first key
    lies past the tile's last valid query are neither copied nor
    computed, and a tile of chunk padding (first row at or past
    ``total_len``) copies nothing and writes its zeros. The grid stays
    static; a skipped step costs only the pipeline's bookkeeping.

    q_ref: [block_q * G, hd] — row r is query token ``r // G`` of this
    chunk, group member ``r % G``; its absolute position is ``prefix_len +
    chunk_start + r // G``. The causal mask admits key positions <= the
    query position (shared prefix + already-written suffix); q rows past the
    valid suffix have position >= total_len and mask out entirely (their
    output is the defined zero and the engine never reads them).
    len_ref: [2] (SMEM) = (prefix_len, total_len = prefix_len + suffix_len).
    """
    k_refs = refs[:pages_per_block]
    v_refs = refs[pages_per_block:2 * pages_per_block]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages_per_block:]
    c, j = pl.program_id(1), pl.program_id(2)
    bt = pages_per_block * page_size
    prefix = len_ref[0]
    total = len_ref[1]
    kv_end = _tile_kv_end(prefix + c * block_q, total, block_q, jnp)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * bt < kv_end)
    def _walk():
        q = q_ref[...]                                    # [block_q*G, hd]
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)   # [bt, hd]
        if q.dtype != k.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        v = jnp.concatenate([r[...] for r in v_refs], axis=0)
        block_start = j * bt
        # zero value rows past the tile's frontier (later suffix tokens,
        # stale pages, the trash page, table padding) before they can meet
        # the accumulators: 0 * NaN would poison the p @ v product
        vpos = block_start + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        v = jnp.where(vpos < kv_end, v.astype(jnp.float32), 0.0)
        qpos = (prefix + c * block_q
                + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0)
                // group)
        kpos = block_start + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        valid = (kpos <= qpos) & (qpos < total)           # causal + q padding
        _softmax_accumulate(q, k, v, valid, m_ref, l_ref, acc_ref,
                            scale=scale)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def paged_append_attention_pallas(q, k_arena, v_arena, page_table, lens, *,
                                  block_q: int = 128, interpret: bool = True):
    """Chunked paged append attention (prefix-cached suffix prefill).

    q [S, H, hd] — S suffix tokens (padded; multiple of 8) whose token i
    sits at absolute position ``prefix_len + i``; arenas
    [P, KV, page_size, hd]; page_table [n_pages] int32 physical page ids for
    ONE request (batch-1 admission path); lens [2] int32 =
    (prefix_len, total_len), scalar-prefetched beside the page table.
    Returns [S, H, hd].

    The grid is (KV, S / block_q, n_blocks): each program attends one
    ``[block_q * G, hd]`` query tile to one KV block of ``pages_per_block``
    physical pages (:func:`_append_tiling`), chasing the page table over
    prefix AND suffix pages with the causal mask applied inside the tile —
    so a request that shares its first ``prefix_len`` tokens reads the
    prefix KV another request wrote, without ever materializing a
    contiguous copy. Each tile walks only the blocks up to its causal
    frontier (:func:`append_walk` counts them). ``block_q`` is clamped to
    divide S at a multiple of 8.
    """
    S, H, hd = q.shape
    _, KV, page_size, _ = k_arena.shape
    n_pages = page_table.shape[0]
    G = H // KV
    if S % 8:
        raise ValueError(
            f"suffix length {S} must be padded to a multiple of 8 "
            "(VPU/MXU sublane layout)")
    block_q, ppb, n_blocks = _append_tiling(S, n_pages, page_size, block_q)
    n_qc = S // block_q
    scale = 1.0 / (hd ** 0.5)

    # [S, H, hd] -> [KV, n_qc, block_q * G, hd]: kv-head-major, rows flatten
    # (token-in-chunk, group) so row r of a tile is token r // G
    qg = (q.reshape(S, KV, G, hd).transpose(1, 0, 2, 3)
          .reshape(KV, n_qc, block_q * G, hd))
    lens = lens.astype(jnp.int32)
    # a last block that overhangs the table reads page 0 there: its
    # positions lie past total_len and mask out
    page_table = jnp.pad(page_table.astype(jnp.int32),
                         (0, n_blocks * ppb - n_pages))
    tiles, bases = _append_walk_steps(lens[0], lens[1], n_qc, n_blocks,
                                      block_q, ppb, page_size)

    def page_spec(p):
        def index(h, c, j, pt, lens, tiles, bases):
            return (pt[bases[c * n_blocks + j] + p], h, 0, 0)
        return pl.BlockSpec((None, None, page_size, hd), index)

    pages = [page_spec(p) for p in range(ppb)]
    kernel = functools.partial(_paged_append_attn_kernel,
                               pages_per_block=ppb, page_size=page_size,
                               block_q=block_q, group=G, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,           # page table, lens, the walk's steps
        grid=(KV, n_qc, n_blocks),
        in_specs=[pl.BlockSpec((None, None, block_q * G, hd),
                               lambda h, c, j, pt, lens, tiles, bases:
                               (h, tiles[c], 0, 0)),
                  *pages, *pages],
        out_specs=pl.BlockSpec((None, None, block_q * G, hd),
                               lambda h, c, j, *_: (h, c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q * G, 1), jnp.float32),
            pltpu.VMEM((block_q * G, 1), jnp.float32),
            pltpu.VMEM((block_q * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, n_qc, block_q * G, hd), q.dtype),
        interpret=interpret,
    )(page_table, lens, tiles, bases, qg, *[k_arena] * ppb, *[v_arena] * ppb)
    return (out.reshape(KV, n_qc, block_q, G, hd)
            .transpose(1, 2, 0, 3, 4).reshape(S, H, hd))
