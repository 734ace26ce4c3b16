"""Kernel microbenchmarks: Pallas (interpret mode on CPU) vs pure-jnp ref.

On CPU interpret mode measures Python-level emulation (NOT TPU perf); the
derived column reports the kernel's analytic FLOPs so the roofline math can
be checked. On a real TPU backend the same harness times the compiled
kernels.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels.decode_attention.kernel import (
    decode_attention_pallas, paged_decode_attention_pallas,
)
from repro.kernels.decode_attention.ref import (
    decode_attention_ref, paged_decode_attention_ref,
)
from repro.kernels.retrieval_topk.kernel import retrieval_topk_pallas
from repro.kernels.retrieval_topk.ref import retrieval_topk_ref
from repro.kernels.rbf.kernel import rbf_matrix_pallas
from repro.kernels.rbf.ref import rbf_matrix_ref


def _time(fn, *args, iters: int = 5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6   # us


def run(quick: bool = False):
    rows = []
    key = jax.random.PRNGKey(0)

    B, H, KV, hd, S = 4, 8, 2, 128, 1024 if not quick else 256
    q = jax.random.normal(key, (B, H, hd), jnp.float32)
    k = jax.random.normal(key, (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(key, (B, S, KV, hd), jnp.float32)
    # the kernel reads kv-head-major caches; lay them out before timing
    k_t, v_t = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    lens = jnp.full((B,), S, jnp.int32)
    flops = 4 * B * H * hd * S
    rows.append({
        "name": "decode_attention/pallas-interpret",
        "us_per_call": round(_time(decode_attention_pallas, q, k_t, v_t,
                                   lens), 1),
        "derived_flops": flops,
    })
    rows.append({
        "name": "decode_attention/jnp-ref",
        "us_per_call": round(_time(decode_attention_ref, q, k, v, lens), 1),
        "derived_flops": flops,
    })

    # paged variant at the same (B, H, KV, hd, S) geometry: S split into
    # page_size chunks scattered across a 2x-overprovisioned arena
    ps = 16
    n_pages = S // ps
    P = 2 * B * n_pages + 1
    rng = np.random.default_rng(0)
    perm = rng.permutation(np.arange(1, P))[: B * n_pages]
    pt = jnp.asarray(perm.reshape(B, n_pages).astype(np.int32))
    k_arena = jax.random.normal(key, (P, KV, ps, hd), jnp.float32)
    v_arena = jax.random.normal(key, (P, KV, ps, hd), jnp.float32)
    flops = 4 * B * H * hd * S
    rows.append({
        "name": "paged_decode_attention/pallas-interpret",
        "us_per_call": round(_time(paged_decode_attention_pallas,
                                   q, k_arena, v_arena, pt, lens), 1),
        "derived_flops": flops,
    })
    rows.append({
        "name": "paged_decode_attention/jnp-ref",
        "us_per_call": round(_time(paged_decode_attention_ref,
                                   q, k_arena, v_arena, pt, lens), 1),
        "derived_flops": flops,
    })

    N, D, K = (4096 if not quick else 1024), 384, 5
    emb = jax.random.normal(key, (N, D), jnp.float32)
    qv = jax.random.normal(key, (D,), jnp.float32)
    flops = 2 * N * D
    rows.append({
        "name": "retrieval_topk/pallas-interpret",
        "us_per_call": round(_time(
            lambda e, x: retrieval_topk_pallas(e, x, K), emb, qv), 1),
        "derived_flops": flops,
    })
    rows.append({
        "name": "retrieval_topk/jnp-ref",
        "us_per_call": round(_time(
            lambda e, x: retrieval_topk_ref(e, x, K), emb, qv), 1),
        "derived_flops": flops,
    })

    M = 512 if not quick else 128
    x1 = jax.random.normal(key, (M, 11), jnp.float32)
    flops = 2 * M * M * 11
    rows.append({
        "name": "rbf/pallas-interpret",
        "us_per_call": round(_time(
            lambda a: rbf_matrix_pallas(a, a, 1.0, 1.0), x1), 1),
        "derived_flops": flops,
    })
    rows.append({
        "name": "rbf/jnp-ref",
        "us_per_call": round(_time(
            lambda a: rbf_matrix_ref(a, a, 1.0, 1.0), x1), 1),
        "derived_flops": flops,
    })
    emit(rows, "kernels_bench")
    return rows


if __name__ == "__main__":
    run()
