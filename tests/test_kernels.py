"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention.kernel import (
    append_walk, decode_attention_pallas, paged_append_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, gather_pages, paged_append_attention_ref,
    paged_decode_attention_ref,
)
from repro.kernels.retrieval_topk.kernel import retrieval_topk_pallas
from repro.kernels.retrieval_topk.ref import retrieval_topk_ref
from repro.kernels.rbf.kernel import rbf_matrix_pallas
from repro.kernels.rbf.ref import rbf_matrix_ref


def _kv_major(cache):
    """[B, S, KV, hd] (the oracle's layout) -> [B, KV, S, hd] (the kernel's)."""
    return cache.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("B,H,KV,hd,S,block_s", [
    (1, 4, 1, 64, 128, 64),
    (2, 8, 2, 128, 512, 128),
    (3, 14, 2, 64, 256, 256),      # qwen2-0.5b geometry
    (2, 8, 4, 256, 384, 128),      # gemma3 geometry
    (1, 16, 16, 128, 512, 512),    # MHA, single block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, KV, hd, S, block_s, dtype):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(42), 4)
    q = jax.random.normal(k1, (B, H, hd), dtype)
    k = jax.random.normal(k2, (B, S, KV, hd), dtype)
    v = jax.random.normal(k3, (B, S, KV, hd), dtype)
    lengths = jax.random.randint(k4, (B,), 1, S + 1)
    out = decode_attention_pallas(q, _kv_major(k), _kv_major(v), lengths,
                                  block_s=block_s)
    ref = decode_attention_ref(q, k, v, lengths)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_decode_attention_length_mask_strict():
    """Cache contents beyond `length` must not influence the output."""
    B, H, KV, hd, S = 1, 4, 2, 64, 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, hd))
    lengths = jnp.array([40])
    out1 = decode_attention_pallas(q, _kv_major(k), _kv_major(v), lengths)
    k2 = k.at[:, 40:].set(999.0)
    v2 = v.at[:, 40:].set(-999.0)
    out2 = decode_attention_pallas(q, _kv_major(k2), _kv_major(v2), lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


@pytest.mark.parametrize("S,block_s", [
    (64, 256),     # S < block_s: used to collapse to a zero-size seq grid
    (100, 256),    # S < block_s AND not an 8-multiple
    (4, 256),      # S smaller than the minimum 8-row tile
    (40, 16),      # ragged tail: S not a multiple of block_s
])
def test_decode_attention_block_clamp_regression(S, block_s):
    """ops hardcoding block_s=256 must not yield S // block_s == 0 programs
    (or silently drop a ragged tail) for short caches."""
    B, H, KV, hd = 2, 4, 2, 64
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(k1, (B, H, hd))
    k = jax.random.normal(k2, (B, S, KV, hd))
    v = jax.random.normal(k3, (B, S, KV, hd))
    lengths = jnp.array([S, max(1, S - 3)])
    out = decode_attention_pallas(q, _kv_major(k), _kv_major(v), lengths,
                                  block_s=block_s)
    ref = decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    # the public dispatch with its default block_s must agree too
    out2 = da_ops.decode_attention(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("length", ["zero", "full", "ragged"])
def test_decode_attention_length_edges(length):
    """length=0 (defined: zeros), length=S, and length not a multiple of
    block_s must all match the oracle."""
    B, H, KV, hd, S, bs = 2, 4, 2, 64, 128, 32
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k1, (B, H, hd))
    k = jax.random.normal(k2, (B, S, KV, hd))
    v = jax.random.normal(k3, (B, S, KV, hd))
    lengths = {"zero": jnp.array([0, 0]),
               "full": jnp.array([S, S]),
               "ragged": jnp.array([bs - 5, S - 7])}[length]
    out = decode_attention_pallas(q, _kv_major(k), _kv_major(v), lengths,
                                  block_s=bs)
    ref = decode_attention_ref(q, k, v, lengths)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    if length == "zero":
        assert (np.asarray(out) == 0).all()


# ---------------------------------------------------------------------------
# Paged flash-decode
# ---------------------------------------------------------------------------

def _ragged_paged_cache(B, P, ps, KV, hd, pages_per_row, seed=0):
    """Random [P, KV, ps, hd] arenas + page tables with distinct physical
    pages per row (scattered, unordered) and trash-page-0 padding."""
    rng = np.random.default_rng(seed)
    k_arena = jnp.asarray(rng.normal(size=(P, KV, ps, hd)).astype(np.float32))
    v_arena = jnp.asarray(rng.normal(size=(P, KV, ps, hd)).astype(np.float32))
    n_pages = max(pages_per_row)
    pt = np.zeros((B, n_pages), np.int32)
    perm = rng.permutation(np.arange(1, P))
    used = 0
    for b, n in enumerate(pages_per_row):
        pt[b, :n] = perm[used:used + n]
        used += n
    return k_arena, v_arena, jnp.asarray(pt)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_matches_paged_ref(dtype):
    B, H, KV, hd, ps = 3, 8, 2, 64, 16
    P = 32
    k_arena, v_arena, pt = _ragged_paged_cache(B, P, ps, KV, hd, [6, 3, 1])
    k_arena = k_arena.astype(dtype)
    v_arena = v_arena.astype(dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, hd), dtype)
    lengths = jnp.array([6 * ps, 3 * ps - 5, 1], jnp.int32)
    out = paged_decode_attention_pallas(q, k_arena, v_arena, pt, lengths)
    ref = paged_decode_attention_ref(q, k_arena, v_arena, pt, lengths)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_paged_matches_contiguous_oracle_ragged_tables():
    """Paged kernel output on a scattered arena == the contiguous oracle on
    the logically reassembled cache, to fp32 tolerance."""
    B, H, KV, hd, ps = 4, 8, 4, 64, 8
    P = 64
    k_arena, v_arena, pt = _ragged_paged_cache(B, P, ps, KV, hd,
                                               [7, 5, 2, 1], seed=3)
    n_pages = pt.shape[1]
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, hd))
    lengths = jnp.array([7 * ps, 5 * ps - 3, ps + 1, 0], jnp.int32)
    out = paged_decode_attention_pallas(q, k_arena, v_arena, pt, lengths)
    # reassemble by hand (not through gather_pages, which the paged ref uses)
    k_c = k_arena[pt].transpose(0, 1, 3, 2, 4).reshape(B, n_pages * ps, KV, hd)
    v_c = v_arena[pt].transpose(0, 1, 3, 2, 4).reshape(B, n_pages * ps, KV, hd)
    np.testing.assert_array_equal(np.asarray(gather_pages(k_arena, pt)),
                                  np.asarray(k_c))
    ref = decode_attention_ref(q, k_c, v_c, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert (np.asarray(out)[3] == 0).all()        # length-0 row contract


def test_paged_trash_page_contents_never_leak():
    """Whatever lives in the trash page (id 0) and in pages past a row's
    valid length must not influence the output."""
    B, H, KV, hd, ps = 2, 4, 2, 64, 16
    P = 16
    k_arena, v_arena, pt = _ragged_paged_cache(B, P, ps, KV, hd, [4, 2])
    q = jax.random.normal(jax.random.PRNGKey(4), (B, H, hd))
    lengths = jnp.array([4 * ps - 9, 2 * ps - 1], jnp.int32)
    out1 = paged_decode_attention_pallas(q, k_arena, v_arena, pt, lengths)
    k2 = k_arena.at[0].set(999.0)                 # poison trash page
    v2 = v_arena.at[0].set(-999.0)
    # poison the tail of each row's last valid page too
    k2 = k2.at[pt[0, 3], :, ps - 9:].set(777.0)
    v2 = v2.at[pt[0, 3], :, ps - 9:].set(-777.0)
    out2 = paged_decode_attention_pallas(q, k2, v2, pt, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# ---------------------------------------------------------------------------
# Paged append attention (chunked suffix prefill)
# ---------------------------------------------------------------------------

def _append_case(P, ps, KV, hd, n_pages, seed=0):
    rng = np.random.default_rng(seed)
    k_arena = jnp.asarray(rng.normal(size=(P, KV, ps, hd)).astype(np.float32))
    v_arena = jnp.asarray(rng.normal(size=(P, KV, ps, hd)).astype(np.float32))
    pt = np.zeros(n_pages, np.int32)
    perm = rng.permutation(np.arange(1, P))
    pt[:] = perm[:n_pages]
    return k_arena, v_arena, jnp.asarray(pt)


@pytest.mark.parametrize("H,KV,hd,ps,S,prefix,suffix,block_q", [
    (8, 2, 64, 16, 64, 21, 33, 16),     # ragged prefix/suffix, small chunks
    (14, 2, 64, 16, 96, 0, 96, 128),    # full prefill (no prefix), clamp bq
    (8, 4, 128, 8, 32, 40, 7, 32),      # long prefix, tiny suffix + padding
    (4, 4, 64, 32, 40, 32, 40, 128),    # MHA, page-aligned prefix, bq->40
    # multi-page KV blocks (512 tokens: 32 pages of 16, 4 of 128)
    (8, 2, 64, 16, 64, 500, 40, 32),    # unaligned prefix, suffix crosses
    (14, 2, 64, 16, 256, 3584, 48, 32), # prefix hit: most tiles padding
    (8, 2, 64, 16, 48, 10, 30, 16),     # table shorter than one block
    (8, 1, 128, 128, 256, 300, 200, 128),  # page size 128, ragged table
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_append_matches_ref(H, KV, hd, ps, S, prefix, suffix, block_q,
                                  dtype):
    n_pages = -(-(prefix + suffix) // ps) + 1
    P = max(24, n_pages + 1)
    k_arena, v_arena, pt = _append_case(P, ps, KV, hd, n_pages)
    k_arena = k_arena.astype(dtype)
    v_arena = v_arena.astype(dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (S, H, hd), dtype)
    lens = jnp.asarray([prefix, prefix + suffix], jnp.int32)
    out = paged_append_attention_pallas(q, k_arena, v_arena, pt, lens,
                                        block_q=block_q)
    ref = paged_append_attention_ref(q, k_arena, v_arena, pt,
                                     jnp.int32(prefix),
                                     jnp.int32(prefix + suffix))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)
    # padded q rows (beyond the valid suffix) are defined zeros
    if suffix < S:
        assert (np.asarray(out, np.float32)[suffix:] == 0).all()


def test_paged_append_rejects_unpadded_suffix():
    """S not a multiple of 8 violates the sublane-layout contract and must
    raise a clear error, not derail the block_q clamp."""
    k_arena, v_arena, pt = _append_case(8, 16, 2, 64, 2)
    q = jax.random.normal(jax.random.PRNGKey(0), (20, 4, 64))
    with pytest.raises(ValueError, match="multiple of 8"):
        paged_append_attention_pallas(q, k_arena, v_arena, pt,
                                      jnp.asarray([0, 20], jnp.int32))


def test_paged_append_last_row_equals_decode():
    """The append kernel's last valid row must equal the decode kernel run
    on that single token — they are the same attention at chunk size 1."""
    H, KV, hd, ps = 8, 2, 64, 16
    prefix, suffix = 19, 24
    n_pages = -(-(prefix + suffix) // ps)
    k_arena, v_arena, pt = _append_case(32, ps, KV, hd, n_pages, seed=5)
    q = jax.random.normal(jax.random.PRNGKey(2), (suffix, H, hd))
    lens = jnp.asarray([prefix, prefix + suffix], jnp.int32)
    out = paged_append_attention_pallas(q, k_arena, v_arena, pt, lens,
                                        block_q=8)
    dec = paged_decode_attention_pallas(
        q[suffix - 1][None], k_arena, v_arena, pt[None],
        jnp.asarray([prefix + suffix], jnp.int32))
    np.testing.assert_allclose(np.asarray(out)[suffix - 1], np.asarray(dec)[0],
                               atol=1e-5, rtol=1e-5)


def test_paged_append_causal_and_stale_page_masking():
    """Keys at positions > the query's (later suffix tokens) and stale data
    beyond total_len — including the trash page — must not leak in."""
    H, KV, hd, ps = 4, 2, 64, 16
    prefix, suffix = 16, 9
    n_pages = 3
    k_arena, v_arena, pt = _append_case(16, ps, KV, hd, n_pages, seed=7)
    q = jax.random.normal(jax.random.PRNGKey(3), (16, H, hd))
    lens = jnp.asarray([prefix, prefix + suffix], jnp.int32)
    out1 = paged_append_attention_pallas(q, k_arena, v_arena, pt, lens)
    # poison everything at/after total_len plus the whole trash page
    total = prefix + suffix
    k2 = k_arena.at[0].set(999.0)
    v2 = v_arena.at[0].set(-999.0)
    k2 = k2.at[pt[1], :, total - ps:].set(777.0)
    v2 = v2.at[pt[1], :, total - ps:].set(-777.0)
    k2 = k2.at[pt[2]].set(555.0)
    v2 = v2.at[pt[2]].set(-555.0)
    out2 = paged_append_attention_pallas(q, k2, v2, pt, lens)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_paged_append_never_reads_past_a_tiles_frontier():
    """A query tile reads no key past its last valid query: per tile, NaN
    in every position from its frontier on (later suffix tokens, the pages
    past total_len, whole blocks past it), in the trash page (the table's
    tail and the kernel's table padding point there) leaves that tile's
    rows as they were. Tiles wholly past total_len (chunk padding) read
    nothing, so their rows stay zero with every key NaN. NaN, not a large
    finite value: a masked value row that reached p @ v unzeroed would
    leak 0 * NaN."""
    H, KV, hd, ps, block_q = 8, 2, 64, 16, 32
    prefix, suffix, S, n_pages = 500, 100, 192, 70
    total = prefix + suffix
    k_arena, v_arena, pt = _append_case(96, ps, KV, hd, n_pages, seed=11)
    pt = pt.at[-(-total // ps):].set(0)       # unused table tail -> trash
    q = jax.random.normal(jax.random.PRNGKey(4), (S, H, hd))
    lens = jnp.asarray([prefix, total], jnp.int32)
    clean = np.asarray(paged_append_attention_pallas(
        q, k_arena, v_arena, pt, lens, block_q=block_q))
    assert np.isfinite(clean).all()
    pos = np.arange(n_pages * ps)
    pages, offs = np.asarray(pt)[pos // ps], pos % ps
    for c in range(S // block_q):
        first = prefix + c * block_q
        frontier = min(first + block_q, total) if first < total else 0
        dead = pos >= frontier
        k2 = k_arena.at[0].set(jnp.nan).at[pages[dead], :, offs[dead]].set(
            jnp.nan)
        v2 = v_arena.at[0].set(jnp.nan).at[pages[dead], :, offs[dead]].set(
            jnp.nan)
        out = np.asarray(paged_append_attention_pallas(
            q, k2, v2, pt, lens, block_q=block_q))
        rows = slice(c * block_q, (c + 1) * block_q)
        np.testing.assert_allclose(out[rows], clean[rows], atol=1e-6)
        if not frontier:
            assert (out[rows] == 0).all()


def _walk_brute(prefix, total, chunk, n_pages, ps, block_q=128, bt=512):
    """(tile, block) pairs holding at least one valid (query, key) pair:
    query position < total and key position <= query position."""
    walked = 0
    for c in range(chunk // block_q):
        qpos = prefix + c * block_q + np.arange(block_q)
        qpos = qpos[qpos < total]
        for b in range(-(-n_pages * ps // bt)):
            kpos = b * bt + np.arange(bt)
            walked += bool((kpos[None, :] <= qpos[:, None]).any())
    return walked


@pytest.mark.parametrize("prefix,total,n_pages", [
    (3584, 3632, 256),      # edge prefix hit: 48-token question
    (0, 2048, 256),         # edge miss, first chunk
    (2048, 3620, 256),      # edge miss, final chunk
    (6144, 8192, 1024),     # cloud, a middle chunk of a 9k context
    (8192, 9017, 1024),     # cloud, a final chunk
])
def test_append_walk_counts_the_live_pairs(prefix, total, n_pages):
    chunk, ps = 2048, 16
    walked, grid = append_walk(prefix, total, chunk, n_pages, ps)
    assert walked == _walk_brute(prefix, total, chunk, n_pages, ps)
    assert grid == (chunk // 128) * (n_pages // 32)


@pytest.mark.parametrize("N,D,k,block_n,n_valid", [
    (100, 384, 5, 64, None),
    (1000, 384, 5, 256, 900),
    (513, 128, 8, 512, 513),
    (64, 384, 3, 64, 10),
    (2048, 256, 1, 512, None),
])
def test_retrieval_topk_sweep(N, D, k, block_n, n_valid):
    key = jax.random.PRNGKey(7)
    emb = jax.random.normal(key, (N, D), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(8), (D,), jnp.float32)
    v, i = retrieval_topk_pallas(emb, q, k, block_n=block_n, n_valid=n_valid)
    vr, ir = retrieval_topk_ref(emb, q, k, n_valid=n_valid)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), atol=1e-4)
    assert (np.asarray(i) == np.asarray(ir)).all()


@pytest.mark.parametrize("M,N,D", [(10, 10, 7), (300, 200, 11),
                                   (128, 128, 384), (257, 65, 16)])
@pytest.mark.parametrize("ls,sv", [(1.0, 1.0), (0.5, 2.0), (3.0, 0.25)])
def test_rbf_sweep(M, N, D, ls, sv):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x1 = jax.random.normal(k1, (M, D))
    x2 = jax.random.normal(k2, (N, D))
    K = rbf_matrix_pallas(x1, x2, ls, sv)
    Kr = rbf_matrix_ref(x1, x2, ls, sv)
    np.testing.assert_allclose(np.asarray(K), np.asarray(Kr),
                               atol=1e-5, rtol=1e-5)


def test_rbf_diagonal_is_signal_var():
    x = jax.random.normal(jax.random.PRNGKey(0), (50, 9))
    K = rbf_matrix_pallas(x, x, 1.7, 0.8)
    np.testing.assert_allclose(np.asarray(jnp.diagonal(K)), 0.8, atol=1e-5)
