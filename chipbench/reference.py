"""Plain float32 forward pass of a dense Qwen2 decoder (arXiv:2407.10671).

Straight ``jax.numpy`` under ``default_matmul_precision("highest")``: no
kernels, no cache, no paging, no batching tricks beyond right padding. It
imports nothing of the program. Weights come from :mod:`chipbench.weights`,
drawn again from the seed one layer at a time and upcast from the bfloat16
they are served in, so the reference of a large share fits beside its
activations.

Per layer (pre-norm): ``x += o(attn(rope(q), rope(k), v))`` with q/k/v
biases and grouped-query attention, then ``x += down(silu(gate) * up)``;
RMSNorm is ``x / sqrt(mean(x^2) + eps) * w``. RoPE rotates the two halves
of each head (the Qwen2 / GPT-NeoX convention). Logits are the final
normed state times the head (the embedding's transpose when tied).

A chip's tensor-parallel share is computed as such: the heads, FFN columns
and vocabulary rows this chip holds, with nothing added for the others.

``quant="int8"`` gives the control: every matrix is rounded to int8 with
one absmax scale per output channel, and the pass is otherwise the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

_MATS = ("wq", "wk", "wv", "wo", "wi_gate", "wi_up", "w_down")
# contraction axes of each matrix, the rest are output channels
_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "wi_gate": (0,), "wi_up": (0,), "w_down": (0,),
            "embed": (1,), "lm_head": (0,)}


def int8_roundtrip(w, in_axes):
    """Round ``w`` to int8 with one absmax scale per output channel."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [N, S, heads, hd]; rotate halves by angle pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _q_block(n, heads, s):
    """Query rows per attention block: keeps the scores near 1 GiB."""
    b = 1 << max(6, int(np.log2(max(1, (1 << 28) // max(1, n * heads * s)))))
    while s % b:
        b //= 2
    return max(b, 1)


def attention(x, w, eps, theta):
    """The attention block's addition to the residual x [N, S, d]."""
    N, S, d = x.shape
    H, hd = w["wq"].shape[1], w["wq"].shape[2]
    KV = w["wk"].shape[1]
    G = H // KV
    pos = jnp.arange(S)
    h = _rms(x, w["ln1"], eps)
    q = _rope(jnp.einsum("nsd,dhe->nshe", h, w["wq"]) + w["bq"], pos, theta)
    k = _rope(jnp.einsum("nsd,dhe->nshe", h, w["wk"]) + w["bk"], pos, theta)
    v = jnp.einsum("nsd,dhe->nshe", h, w["wv"]) + w["bv"]
    qb = _q_block(N, H, S)
    qs = q.reshape(N, S // qb, qb, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def block(args):
        i, qi = args                               # qi [N, qb, KV, G, hd]
        s = jnp.einsum("nqkgd,nskd->nkgqs", qi, k) / np.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nkgqs,nskd->nqkgd", p, v)

    a = jax.lax.map(block, (jnp.arange(S // qb), qs))
    a = a.transpose(1, 0, 2, 3, 4, 5).reshape(N, S, H, hd)
    return jnp.einsum("nshe,hed->nsd", a, w["wo"])


def ffn(x, w, eps):
    """The SwiGLU block's addition to the residual x [N, S, d]."""
    h = _rms(x, w["ln2"], eps)
    return (jax.nn.silu(h @ w["wi_gate"]) * (h @ w["wi_up"])) @ w["w_down"]


def f32_layer_weights(key, layer, dims, init, quant):
    """Layer ``layer``'s served (bf16) weights, upcast to float32; norms
    as ``1 + offset``; with ``quant="int8"`` every matrix rounded."""
    w = {k: v.astype(jnp.float32)
         for k, v in W.layer_weights(key, layer, dims, init).items()}
    w["ln1"] = 1.0 + w["ln1"]
    w["ln2"] = 1.0 + w["ln2"]
    if quant == "int8":
        w.update({m: int8_roundtrip(w[m], _IN_AXES[m]) for m in _MATS})
    return w


@functools.lru_cache(maxsize=None)
def _programs(dims_items, init_items, eps, theta, quant):
    """Jitted pieces of the pass for one configuration: each layer draws
    its own weights from the key inside the program, so a layer compiles
    once and no layer's weights outlive it."""
    dims, init = dict(dims_items), dict(init_items)

    def layer(key, i, x):
        with jax.default_matmul_precision("highest"):
            w = f32_layer_weights(key, i, dims, init, quant)
            x = x + attention(x, w, eps, theta)
            return x + ffn(x, w, eps)

    def top(key):
        t = {k: v.astype(jnp.float32)
             for k, v in W.top_weights(key, dims, init).items()}
        t["final_norm"] = 1.0 + t["final_norm"]
        if quant == "int8":
            for m in ("embed", "lm_head"):
                if m in t:
                    t[m] = int8_roundtrip(t[m], _IN_AXES[m])
        t["head"] = t.pop("lm_head") if "lm_head" in t else t["embed"].T
        return t

    def embed(table, toks):
        return jnp.take(table, toks, axis=0)

    def final(x, norm):
        return _rms(x, norm, eps)

    return (jax.jit(layer, donate_argnums=(2,)), jax.jit(top),
            jax.jit(embed), jax.jit(final))


def final_states(seed: int, model: dict, init: dict, rows, quant=None):
    """Final normed hidden states of each token list in ``rows``.

    Rows are right-padded to one length (a multiple of 2048), so every layer
    runs one compiled program and few lengths ever compile; padding sits after each row and causal
    attention keeps it out of the real positions. Returns
    ``(states [N, S, d], top weights)``."""
    dims = model_dims(model)
    S = max(len(r) for r in rows)
    S = -(-S // 2048) * 2048
    toks = np.zeros((len(rows), S), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r
    layer, top_fn, embed, final = _programs(
        tuple(sorted(dims.items())), tuple(sorted(init.items())),
        float(model["rms_norm_eps"]), float(model["rope_theta"]), quant)
    key = W.base_key(seed)
    top = top_fn(key)
    x = embed(top["embed"], jnp.asarray(toks))
    for i in range(dims["layers"]):
        x = layer(key, jnp.int32(i), x)
    return final(x, top["final_norm"]), top


@functools.partial(jax.jit, static_argnames=("quant_ranked",))
def _gaps(states, head, positions, served, ranked_by, quant_ranked=False):
    """Per position: the reference's best logit minus its logit of the
    served token, and of the token ``ranked_by``'s logits put first."""
    with jax.default_matmul_precision("highest"):
        lg = jnp.take_along_axis(states, positions[..., None], axis=1) @ head
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
    out = [best - got]
    if quant_ranked:
        with jax.default_matmul_precision("highest"):
            lq = (jnp.take_along_axis(ranked_by[0], positions[..., None],
                                      axis=1) @ ranked_by[1])
        top1 = jnp.argmax(lq, axis=-1)
        out.append(best - jnp.take_along_axis(lg, top1[..., None],
                                              axis=-1)[..., 0])
    return out


def token_gaps(states, top, positions, served, control=None):
    """Gaps at each row's positions (lists of equal-or-shorter length,
    padded here to one shape so the program compiles once). ``control``
    is ``(states, top)`` of the control pass. Returns a list of 1-D numpy
    arrays per row: served gaps, and control gaps when asked."""
    n = len(positions)
    P = -(-max(len(p) for p in positions) // 64) * 64
    pos = np.zeros((n, P), np.int32)
    tok = np.zeros((n, P), np.int32)
    for i, (p, t) in enumerate(zip(positions, served)):
        pos[i, :len(p)] = p
        tok[i, :len(t)] = t
    ranked = (control[0], control[1]["head"]) if control else (states, 0)
    res = _gaps(states, top["head"], jnp.asarray(pos), jnp.asarray(tok),
                ranked, quant_ranked=control is not None)
    res = [np.asarray(r) for r in res]
    return [[r[i, :len(positions[i])] for i in range(n)] for r in res]


def logits_at(states_row, top, positions):
    """Logits [P, V] of one row at the given positions, float32."""
    with jax.default_matmul_precision("highest"):
        return states_row[jnp.asarray(positions, jnp.int32)] @ top["head"]


def model_dims(model: dict) -> dict:
    """The sizes this chip holds, from a configuration's ``model`` group:
    a tensor-parallel share divides heads, FFN columns and vocabulary."""
    tp = int(model.get("tensor_parallel", 1))
    hd = model.get("head_dim") or (model["hidden_size"]
                                   // model["num_attention_heads"])
    for k in ("num_attention_heads", "num_key_value_heads",
              "intermediate_size", "vocab_size"):
        if model[k] % tp:
            raise ValueError(f"{k} {model[k]} does not split {tp} ways")
    return {
        "layers": model["num_hidden_layers"], "d": model["hidden_size"],
        "heads": model["num_attention_heads"] // tp,
        "kv_heads": model["num_key_value_heads"] // tp,
        "head_dim": hd, "ffn": model["intermediate_size"] // tp,
        "vocab": model["vocab_size"] // tp,
        "tied": bool(model["tie_word_embeddings"]),
    }
