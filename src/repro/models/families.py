"""Per-family segment constructors for all 10 assigned architectures.

Each family builds a list of :class:`Segment` (plus optional encoder
segments and extra top-level params). See stacks.py for the contract.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention.ops import (
    paged_append_attention, paged_decode_attention,
)
from repro.models import mla as mla_mod
from repro.models import mamba2 as m2
from repro.models import rwkv6 as rw
from repro.models.layers import (
    apply_rope, causal_attention, cross_attention, decode_attention,
    gqa_proj_defs, out_proj, qkv, rms_norm, rms_norm_def, swiglu, swiglu_defs,
)
from repro.models.moe import moe_defs, moe_ffn
from repro.models.pdefs import ParamDef, stack
from repro.models.shardctx import constrain
from repro.models.stacks import Segment

ZERO = lambda: jnp.zeros((), jnp.float32)


def _kv_cache_defs(B: int, S: int, n_kv: int, hd: int, dtype=jnp.bfloat16,
                   quant: bool = False):
    ax = ("batch", "cache_seq", "kv_heads", None)
    if quant:
        # int8 per-(token, head) absmax quantization: ~2x cache memory +
        # HBM-read reduction (the decode read is the serving bottleneck)
        sax = ("batch", "cache_seq", "kv_heads")
        return {
            "k": ParamDef((B, S, n_kv, hd), ax, jnp.int8, init="zeros"),
            "ks": ParamDef((B, S, n_kv), sax, jnp.float32, init="zeros"),
            "v": ParamDef((B, S, n_kv, hd), ax, jnp.int8, init="zeros"),
            "vs": ParamDef((B, S, n_kv), sax, jnp.float32, init="zeros"),
        }
    return {
        "k": ParamDef((B, S, n_kv, hd), ax, dtype, init="zeros"),
        "v": ParamDef((B, S, n_kv, hd), ax, dtype, init="zeros"),
    }


def _kv_arena_defs(P: int, ps: int, n_kv: int, hd: int, dtype=jnp.bfloat16):
    """Paged layout: one global page arena per layer instead of per-slot
    lanes. Logical position t of a request lives at
    ``arena[page_table[slot, t // ps], :, t % ps]``. Pages are kv-head-major
    (``[P, KV, ps, hd]``) so a kernel block of one head's page has
    ``(ps, hd)`` as its last two dims, the tiling the TPU compiler takes."""
    ax = ("pages", "kv_heads", "page_seq", None)
    return {
        "k": ParamDef((P, n_kv, ps, hd), ax, dtype, init="zeros"),
        "v": ParamDef((P, n_kv, ps, hd), ax, dtype, init="zeros"),
    }


def _quantize_kv(kv):
    """[..., hd] -> (int8 [..., hd], scale [...])."""
    amax = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0 + 1e-8
    q = jnp.clip(jnp.round(kv.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _write_ring(cache, kv_new, S: int, W: int):
    """Write the last min(S, W) tokens of kv_new [B,S,...] into ring slots."""
    take = min(S, W)
    idx = (jnp.arange(S - take, S) % W)
    return cache.at[:, idx].set(kv_new[:, -take:].astype(cache.dtype))


def _write_decode(cache, kv1, pos, ring_w: int = 0):
    """Write one token kv1 [B,1,...] at per-row position pos [B]."""
    slot = pos % ring_w if ring_w else pos
    return cache.at[jnp.arange(kv1.shape[0]), slot].set(
        kv1[:, 0].astype(cache.dtype))


# ---------------------------------------------------------------------------
# GQA attention layer (dense / moe / local-global window / qkv-bias)
# ---------------------------------------------------------------------------

def make_attn_layer(cfg: ModelConfig, *, window: int = 0, ffn: str = "dense",
                    dense_ff: int = 0, causal: bool = True, rope: bool = True):
    d, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    dt = cfg.activation_dtype
    theta = cfg.rope_theta if rope else 0.0
    quant = cfg.kv_cache_dtype == "int8"

    def _pack(k, v):
        """kv [B,S,KV,hd] -> cache entry dict (quantized or plain)."""
        if quant:
            qk, sk = _quantize_kv(k)
            qv, sv = _quantize_kv(v)
            return {"k": qk, "ks": sk, "v": qv, "vs": sv}
        return {"k": k.astype(dt), "v": v.astype(dt)}

    def _unpack(ce):
        if quant:
            return (_dequantize_kv(ce["k"], ce["ks"], dt),
                    _dequantize_kv(ce["v"], ce["vs"], dt))
        return ce["k"], ce["v"]

    def defs():
        dd = {
            "ln1": rms_norm_def(d),
            "attn": gqa_proj_defs(d, H, KV, hd, cfg.qkv_bias, dt),
            "ln2": rms_norm_def(d),
        }
        if ffn == "moe":
            dd["ffn"] = moe_defs(d, cfg.moe, dt)
        else:
            dd["ffn"] = swiglu_defs(d, dense_ff or cfg.d_ff, dt)
        return dd

    def _ffn_apply(p, x):
        h = rms_norm(x, p["ln2"], cfg.rms_eps)
        if ffn == "moe":
            y, aux = moe_ffn(p["ffn"], h, cfg.moe, dtype=dt)
            return x + y, aux
        return x + swiglu(p["ffn"], h), ZERO()

    def fwd_full(p, x, ctx):
        pos = ctx["positions"]
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        q, k, v = qkv(p["attn"], h)
        q = apply_rope(q, pos, theta)
        k = apply_rope(k, pos, theta)
        a = causal_attention(q, k, v, n_kv=KV, window=window,
                             q_chunk=cfg.q_chunk)
        x = x + out_proj(p["attn"], a)
        x, aux = _ffn_apply(p, x)
        ce = {}
        if ctx["mode"] == "prefill":
            S_cache = ctx["cache_len"]
            B, S = k.shape[0], k.shape[1]
            packed = _pack(k, v)
            cd = _kv_cache_defs(B, min(window, S_cache) if window else S_cache,
                                KV, hd, dt, quant)
            if window and window < S_cache:
                ce = {name: _write_ring(jnp.zeros(cd[name].shape,
                                                  cd[name].dtype),
                                        packed[name], S, window)
                      for name in packed}
            else:
                ce = {name: jnp.zeros(cd[name].shape, cd[name].dtype)
                      .at[:, :S].set(packed[name]) for name in packed}
        return x, ce, aux

    def fwd_decode(p, x1, ctx, ce):
        pos = ctx["positions"]                       # [B]
        h = rms_norm(x1, p["ln1"], cfg.rms_eps)
        q, k, v = qkv(p["attn"], h)                  # [B,1,H,hd]
        q = apply_rope(q, pos[:, None], theta)
        k = apply_rope(k, pos[:, None], theta)
        packed = _pack(k, v)
        if ctx.get("page_table") is not None:
            # paged layout: ce leaves are [P, KV, page_size, hd] arenas;
            # scatter this token at its slot's physical (page, offset) and
            # attend through the page table. The allocator guarantees every
            # active slot owns distinct pages, so the scatter never races;
            # inactive slots park on the trash page (id 0, never read).
            ps_sz = ctx["page_size"]
            pt = ctx["page_table"]                   # [B, n_pages] int32
            phys = jnp.take_along_axis(
                pt, (pos // ps_sz)[:, None], axis=1)[:, 0]
            new_ce = {name: ce[name].at[phys, :, pos % ps_sz].set(
                          packed[name][:, 0].astype(ce[name].dtype))
                      for name in packed}
            a = paged_decode_attention(q[:, 0], new_ce["k"], new_ce["v"],
                                       pt, ctx["lengths"])
        else:
            ring_w = window if (window and ce["k"].shape[1] == window) else 0
            new_ce = {name: _write_decode(ce[name], packed[name], pos, ring_w)
                      for name in packed}
            kc, vc = _unpack(new_ce)
            a = decode_attention(q[:, 0], kc, vc, ctx["lengths"],
                                 n_kv=KV, window=window, ring=bool(ring_w))
        x1 = x1 + out_proj(p["attn"], a[:, None])
        x1, aux = _ffn_apply(p, x1)
        return x1, new_ce, aux

    def cache_defs(B, S):
        S_eff = min(window, S) if window else S
        return _kv_cache_defs(B, S_eff, KV, hd, dt, quant)

    # block-granular paged cache: full-context bf16 GQA only — a ring-buffer
    # window already bounds memory, and int8 paging would need scale arenas
    paged_cache_defs = None
    fwd_append = None
    if not window and not quant:
        def paged_cache_defs(num_pages, page_size):
            return _kv_arena_defs(num_pages, page_size, KV, hd, dt)

        def fwd_append(p, x, ctx, ce):
            """Batch-1 suffix prefill against the page arena: token i of x
            sits at absolute position ``prefix_len + i``. The suffix KV is
            scattered token-granularly at its (physical page, offset) —
            pages the slot owns privately, so writes never race a shared
            prefix page — and attention runs over prefix + suffix through
            the page table. Rows past ``suffix_len`` scatter to the trash
            page and mask out of the attention."""
            ps_sz = ctx["page_size"]
            pt = ctx["page_table"]                   # [n_pages] (one slot)
            prefix_len = ctx["prefix_len"]
            suffix_len = ctx["suffix_len"]
            pos = ctx["positions"]                   # [S] = prefix + arange
            S = x.shape[1]
            h = rms_norm(x, p["ln1"], cfg.rms_eps)
            q, k, v = qkv(p["attn"], h)              # [1,S,H,hd]
            q = apply_rope(q, pos[None], theta)
            k = apply_rope(k, pos[None], theta)
            packed = _pack(k, v)
            phys = jnp.where(jnp.arange(S) < suffix_len,
                             pt[pos // ps_sz], 0)    # padding -> trash page
            off = pos % ps_sz
            new_ce = {name: ce[name].at[phys, :, off].set(
                          packed[name][0].astype(ce[name].dtype))
                      for name in packed}
            a = paged_append_attention(q[0], new_ce["k"], new_ce["v"], pt,
                                       prefix_len, prefix_len + suffix_len)
            x = x + out_proj(p["attn"], a[None])
            x, aux = _ffn_apply(p, x)
            return x, new_ce, aux

    return defs, fwd_full, fwd_decode, cache_defs, paged_cache_defs, \
        fwd_append


# ---------------------------------------------------------------------------
# MLA attention layer (deepseek) — compressed-latent cache
# ---------------------------------------------------------------------------

def make_mla_layer(cfg: ModelConfig, *, ffn: str = "moe", dense_ff: int = 0):
    d, H = cfg.d_model, cfg.n_heads
    m = cfg.mla
    dt = cfg.activation_dtype

    def defs():
        dd = {
            "ln1": rms_norm_def(d),
            "attn": mla_mod.mla_defs(d, H, m, dt),
            "ln2": rms_norm_def(d),
        }
        if ffn == "moe":
            dd["ffn"] = moe_defs(d, cfg.moe, dt)
        else:
            dd["ffn"] = swiglu_defs(d, dense_ff or cfg.d_ff, dt)
        return dd

    def _ffn_apply(p, x):
        h = rms_norm(x, p["ln2"], cfg.rms_eps)
        if ffn == "moe":
            y, aux = moe_ffn(p["ffn"], h, cfg.moe, dtype=dt)
            return x + y, aux
        return x + swiglu(p["ffn"], h), ZERO()

    def fwd_full(p, x, ctx):
        pos = ctx["positions"]
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        out, (c_kv, k_rope) = mla_mod.mla_attention_prefill(
            p["attn"], h, m, positions=pos, theta=cfg.rope_theta,
            eps=cfg.rms_eps, q_chunk=cfg.q_chunk)
        x = x + out
        x, aux = _ffn_apply(p, x)
        ce = {}
        if ctx["mode"] == "prefill":
            B, S = c_kv.shape[0], c_kv.shape[1]
            Sc = ctx["cache_len"]
            ck = jnp.zeros((B, Sc, m.kv_lora_rank), dt)
            kr = jnp.zeros((B, Sc, m.qk_rope_dim), dt)
            ce = {"ckv": ck.at[:, :S].set(c_kv.astype(dt)),
                  "kr": kr.at[:, :S].set(k_rope.astype(dt))}
        return x, ce, aux

    def fwd_decode(p, x1, ctx, ce):
        pos = ctx["positions"]
        h = rms_norm(x1, p["ln1"], cfg.rms_eps)
        c_kv, k_rope = mla_mod.mla_latents(p["attn"], h, m, pos[:, None],
                                           cfg.rope_theta, cfg.rms_eps)
        new_ckv = _write_decode(ce["ckv"], c_kv, pos)
        new_kr = _write_decode(ce["kr"], k_rope, pos)
        out = mla_mod.mla_attention_decode(
            p["attn"], h, m, new_ckv, new_kr, ctx["lengths"],
            positions=pos, theta=cfg.rope_theta, eps=cfg.rms_eps)
        x1 = x1 + out
        x1, aux = _ffn_apply(p, x1)
        return x1, {"ckv": new_ckv, "kr": new_kr}, aux

    def cache_defs(B, S):
        ax = ("batch", "cache_seq", None)
        return {"ckv": ParamDef((B, S, m.kv_lora_rank), ax, dt, init="zeros"),
                "kr": ParamDef((B, S, m.qk_rope_dim), ax, dt, init="zeros")}

    return defs, fwd_full, fwd_decode, cache_defs


# ---------------------------------------------------------------------------
# Cross-attention layer (VLM: gated; whisper decoder: ungated)
# ---------------------------------------------------------------------------

def make_cross_layer(cfg: ModelConfig, *, gated: bool, n_mem: int,
                     with_ffn: bool = True):
    d, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    dt = cfg.activation_dtype

    def defs():
        dd = {
            "ln1": rms_norm_def(d),
            "attn": gqa_proj_defs(d, H, KV, hd, cfg.qkv_bias, dt),
        }
        if gated:
            dd["gate_attn"] = ParamDef((1,), (None,), jnp.float32, init="zeros")
            dd["gate_ffn"] = ParamDef((1,), (None,), jnp.float32, init="zeros")
        if with_ffn:
            dd["ln2"] = rms_norm_def(d)
            dd["ffn"] = swiglu_defs(d, cfg.d_ff, dt)
        return dd

    def _mem_kv(p, mem):
        k = jnp.einsum("btd,dhe->bthe", mem, p["attn"]["wk"])
        v = jnp.einsum("btd,dhe->bthe", mem, p["attn"]["wv"])
        if "bk" in p["attn"]:
            k = k + p["attn"]["bk"]
            v = v + p["attn"]["bv"]
        return k, v

    def _apply(p, x, k, v):
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        q = jnp.einsum("bsd,dhe->bshe", h, p["attn"]["wq"])
        if "bq" in p["attn"]:
            q = q + p["attn"]["bq"]
        a = cross_attention(q, k, v, n_kv=KV)
        y = out_proj(p["attn"], a)
        if gated:
            y = jnp.tanh(p["gate_attn"]).astype(y.dtype) * y
        x = x + y
        if with_ffn:
            f = swiglu(p["ffn"], rms_norm(x, p["ln2"], cfg.rms_eps))
            if gated:
                f = jnp.tanh(p["gate_ffn"]).astype(f.dtype) * f
            x = x + f
        return x

    def fwd_full(p, x, ctx):
        k, v = _mem_kv(p, ctx["memory"])
        x = _apply(p, x, k, v)
        ce = {"k": k.astype(dt), "v": v.astype(dt)} if ctx["mode"] == "prefill" else {}
        return x, ce, ZERO()

    def fwd_decode(p, x1, ctx, ce):
        x1 = _apply(p, x1, ce["k"], ce["v"])
        return x1, {"k": ce["k"], "v": ce["v"]}, ZERO()

    def cache_defs(B, S):
        ax = ("batch", "frames", "kv_heads", None)
        return {"k": ParamDef((B, n_mem, KV, hd), ax, dt, init="zeros"),
                "v": ParamDef((B, n_mem, KV, hd), ax, dt, init="zeros")}

    return defs, fwd_full, fwd_decode, cache_defs


def make_bidir_layer(cfg: ModelConfig):
    """Bidirectional self-attention encoder layer (whisper encoder)."""
    d, H, KV = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    dt = cfg.activation_dtype

    def defs():
        return {
            "ln1": rms_norm_def(d),
            "attn": gqa_proj_defs(d, H, KV, hd, cfg.qkv_bias, dt),
            "ln2": rms_norm_def(d),
            "ffn": swiglu_defs(d, cfg.d_ff, dt),
        }

    def fwd_full(p, x, ctx):
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        q, k, v = qkv(p["attn"], h)
        a = cross_attention(q, k, v, n_kv=KV)
        x = x + out_proj(p["attn"], a)
        x = x + swiglu(p["ffn"], rms_norm(x, p["ln2"], cfg.rms_eps))
        return x, {}, ZERO()

    def fwd_decode(p, x1, ctx, ce):
        raise NotImplementedError("encoder layers never run at decode")

    def cache_defs(B, S):
        return {}

    return defs, fwd_full, fwd_decode, cache_defs


# ---------------------------------------------------------------------------
# Mamba2 layer / RWKV6 layer
# ---------------------------------------------------------------------------

def make_mamba_layer(cfg: ModelConfig):
    d, s = cfg.d_model, cfg.ssm
    d_in, H = m2.mamba2_dims(d, s)
    dt = cfg.activation_dtype
    conv_ch = d_in + 2 * s.d_state

    def defs():
        return {"ln": rms_norm_def(d), "mamba": m2.mamba2_defs(d, s, dt)}

    def fwd_full(p, x, ctx):
        h = rms_norm(x, p["ln"], cfg.rms_eps)
        y, final = m2.mamba2_scan(p["mamba"], h, s)
        ce = {}
        if ctx["mode"] == "prefill":
            # conv state: last (W-1) pre-activation conv inputs
            u = _mamba_conv_inputs(p["mamba"], h, s)
            ce = {"state": final,
                  "conv": u[:, -(s.conv_width - 1):].astype(jnp.float32)}
        return x + y, ce, ZERO()

    def fwd_decode(p, x1, ctx, ce):
        h = rms_norm(x1, p["ln"], cfg.rms_eps)
        y, new_state, new_conv = m2.mamba2_step(
            p["mamba"], h, s, ce["state"], ce["conv"].astype(h.dtype))
        return x1 + y, {"state": new_state, "conv": new_conv.astype(jnp.float32)}, ZERO()

    def cache_defs(B, S):
        return {
            "state": ParamDef((B, H, s.d_head, s.d_state),
                              ("batch", "heads", None, None), jnp.float32,
                              init="zeros"),
            "conv": ParamDef((B, s.conv_width - 1, conv_ch),
                             ("batch", None, "ff"), jnp.float32, init="zeros"),
        }

    return defs, fwd_full, fwd_decode, cache_defs


def _mamba_conv_inputs(params, x, s):
    xs = jnp.einsum("bsd,de->bse", x, params["w_x"])
    bc = jnp.einsum("bsd,de->bse", x, params["w_bc"])
    return jnp.concatenate([xs, bc], axis=-1)


def make_rwkv_layer(cfg: ModelConfig):
    d, s = cfg.d_model, cfg.ssm
    H = rw.rwkv6_dims(d, s.d_head)
    dt = cfg.activation_dtype

    def defs():
        dd = rw.rwkv6_defs(d, cfg.d_ff, s.d_head, dt)
        dd["ln1"] = rms_norm_def(d)
        dd["ln2"] = rms_norm_def(d)
        return dd

    def fwd_full(p, x, ctx):
        h = rms_norm(x, p["ln1"], cfg.rms_eps)
        y, S_f, x_tm = rw.time_mix(p["tm"], h, s.d_head, chunk=cfg.rwkv_chunk)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
        y2, x_cm = rw.channel_mix(p["cm"], h2)
        x = x + y2
        ce = {}
        if ctx["mode"] == "prefill":
            ce = {"S": S_f, "x_tm": x_tm, "x_cm": x_cm}
        return x, ce, ZERO()

    def fwd_decode(p, x1, ctx, ce):
        h = rms_norm(x1, p["ln1"], cfg.rms_eps)
        y, S_new, x_tm = rw.time_mix_step(p["tm"], h, s.d_head, ce["S"], ce["x_tm"])
        x1 = x1 + y
        h2 = rms_norm(x1, p["ln2"], cfg.rms_eps)
        y2, x_cm = rw.channel_mix(p["cm"], h2, ce["x_cm"])
        x1 = x1 + y2
        return x1, {"S": S_new, "x_tm": x_tm, "x_cm": x_cm}, ZERO()

    def cache_defs(B, S):
        return {
            "S": ParamDef((B, H, s.d_head, s.d_head),
                          ("batch", "heads", None, None), jnp.float32, init="zeros"),
            "x_tm": ParamDef((B, 1, d), ("batch", None, "embed"), dt, init="zeros"),
            "x_cm": ParamDef((B, 1, d), ("batch", None, "embed"), dt, init="zeros"),
        }

    return defs, fwd_full, fwd_decode, cache_defs


# ---------------------------------------------------------------------------
# Composite units (gemma local/global, vlm self+cross, zamba mamba+shared-attn)
# ---------------------------------------------------------------------------

def make_unit(layer_makers):
    """Compose sub-layers (name, maker_tuple) into one scanned 'unit' layer."""
    def defs():
        return {name: mk[0]() for name, mk in layer_makers}

    def fwd_full(p, x, ctx):
        ces, aux = {}, ZERO()
        for name, mk in layer_makers:
            x, ce, a = mk[1](p[name], x, ctx)
            if ce:
                ces[name] = ce
            aux += a
        return x, ces, aux

    def fwd_decode(p, x1, ctx, ce):
        new, aux = {}, ZERO()
        for name, mk in layer_makers:
            x1, ce2, a = mk[2](p[name], x1, ctx, ce[name])
            if ce2:
                new[name] = ce2
            aux += a
        return x1, new, aux

    def cache_defs(B, S):
        out = {}
        for name, mk in layer_makers:
            cd = mk[3](B, S)
            if cd:
                out[name] = cd
        return out

    return defs, fwd_full, fwd_decode, cache_defs


def make_stacked_sublayer(maker, n: int):
    """A sub-layer that is itself an inner scanned stack of n layers."""
    dfs, f_full, f_dec, cdefs = maker[:4]

    def defs():
        return stack(dfs(), n)

    def fwd_full(p, x, ctx):
        def body(h, pl):
            h2, ce, aux = f_full(pl, h, ctx)
            return h2, (ce, aux)
        x, (ces, auxs) = jax.lax.scan(body, x, p)
        return x, ces, jnp.sum(auxs)

    def fwd_decode(p, x1, ctx, ce):
        def body(h, args):
            pl, cl = args
            h2, c2, aux = f_dec(pl, h, ctx, cl)
            return h2, (c2, aux)
        x1, (ces, auxs) = jax.lax.scan(body, x1, (p, ce))
        return x1, ces, jnp.sum(auxs)

    def cache_defs(B, S):
        cd = cdefs(B, S)
        return stack(cd, n) if cd else {}

    return defs, fwd_full, fwd_decode, cache_defs


__all__ = [
    "make_attn_layer", "make_mla_layer", "make_cross_layer",
    "make_mamba_layer", "make_rwkv_layer", "make_bidir_layer", "make_unit",
    "make_stacked_sublayer", "_kv_cache_defs", "_kv_arena_defs",
]
