"""Random weights from a seed, made by the benchmark, for both sides.

One function draws one layer's weights from ``(seed, layer)``. The program
gets every layer at once, stacked, in its own parameter layout and in the
type it serves (bfloat16), from one jitted call on the device. The plain
reference draws the same layers again, one at a time, after the program's
state is freed, and upcasts them to float32. Neither side reads the other's
arrays, and the program's own initializer is not used.

Scales (all weights normal, mean 0):

- q and k projections: ``qk_gain / sqrt(d)``, so attention scores have a
  standard deviation of about ``qk_gain**2`` and softmax picks out a few
  keys, as in trained models. With flat attention a wrong page or a wrong
  prefix would hardly move the output, and the check could not see it.
- v, o and the FFN: ``1 / sqrt(fan_in)``.
- q/k/v biases ``bias_std``; RMSNorm weights ``1 + N(0, norm_std)`` (the
  program stores the offset from 1).
- embedding ``embed_std``; an untied head ``head_std / sqrt(d)``. Both are
  chosen so that the logits have a standard deviation near 3, as a served
  model's do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A PRNG key for any whole number, including ones past 32 bits."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


def layer_shapes(dims: dict) -> dict:
    """One layer's weight shapes in the program's per-layer layout."""
    d, H, KV, hd, ff = (dims["d"], dims["heads"], dims["kv_heads"],
                        dims["head_dim"], dims["ffn"])
    return {
        "ln1": (d,), "ln2": (d,),
        "wq": (d, H, hd), "wk": (d, KV, hd), "wv": (d, KV, hd),
        "wo": (H, hd, d), "bq": (H, hd), "bk": (KV, hd), "bv": (KV, hd),
        "wi_gate": (d, ff), "wi_up": (d, ff), "w_down": (ff, d),
    }


def _stds(dims: dict, init: dict) -> dict:
    d, H, hd, ff = dims["d"], dims["heads"], dims["head_dim"], dims["ffn"]
    qk = init["qk_gain"] / np.sqrt(d)
    return {
        "ln1": init["norm_std"], "ln2": init["norm_std"],
        "wq": qk, "wk": qk, "wv": 1 / np.sqrt(d), "wo": 1 / np.sqrt(H * hd),
        "bq": init["bias_std"], "bk": init["bias_std"],
        "bv": init["bias_std"],
        "wi_gate": 1 / np.sqrt(d), "wi_up": 1 / np.sqrt(d),
        "w_down": 1 / np.sqrt(ff),
    }


def layer_weights(key, layer, dims: dict, init: dict, dtype=jnp.bfloat16):
    """Layer ``layer``'s weights, drawn from ``key`` alone, as ``dtype``.
    Norm entries are the offset from 1, as the program stores them."""
    lk = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    stds = _stds(dims, init)
    out = {}
    for i, (name, shape) in enumerate(sorted(layer_shapes(dims).items())):
        w = jax.random.normal(jax.random.fold_in(lk, i), shape, jnp.float32)
        out[name] = (w * stds[name]).astype(dtype)
    return out


def top_weights(key, dims: dict, init: dict, dtype=jnp.bfloat16):
    """Embedding, final norm offset and (untied) head."""
    d, V = dims["d"], dims["vocab"]
    tk = jax.random.fold_in(key, 2)
    out = {
        "embed": (jax.random.normal(jax.random.fold_in(tk, 0), (V, d))
                  * init["embed_std"]).astype(dtype),
        "final_norm": (jax.random.normal(jax.random.fold_in(tk, 1), (d,))
                       * init["norm_std"]).astype(dtype),
    }
    if not dims["tied"]:
        out["lm_head"] = (jax.random.normal(jax.random.fold_in(tk, 2), (d, V))
                          * (init["head_std"] / np.sqrt(d))).astype(dtype)
    return out


def program_params(seed: int, dims: dict, init: dict):
    """Every weight, in the program's stacked layout, from one jitted call
    on the default device."""
    L = dims["layers"]

    def make(key):
        layers = jax.vmap(lambda l: layer_weights(key, l, dims, init))(
            jnp.arange(L))
        top = top_weights(key, dims, init)
        params = {
            "embed": top["embed"],
            "final_norm": top["final_norm"],
            "blocks": {
                "ln1": layers["ln1"], "ln2": layers["ln2"],
                "attn": {k: layers[k] for k in
                         ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
                "ffn": {"wi_gate": layers["wi_gate"],
                        "wi_up": layers["wi_up"], "wo": layers["w_down"]},
            },
        }
        if "lm_head" in top:
            params["lm_head"] = top["lm_head"]
        return params

    return jax.jit(make)(base_key(seed))
