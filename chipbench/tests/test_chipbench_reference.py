"""The plain float32 reference, tied to the program on the CPU.

At toy widths, the program's paged prefill and decode (``prefill_paged``
and ``decode_step_paged`` through the page table, the path the engine's
steps run) must give the logits of the reference's full forward pass over
the same tokens. And a tensor-parallel share of a layer must be a share:
the parts that all shares give add up to the uncut layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, weights
from chipbench.run import model_config
from chipbench.tests._tiny import tiny_cell

SEED = 2 ** 31 + 5


def _model(tp=1):
    cfg = tiny_cell()["config"]
    if tp > 1:
        cfg["model"].update(num_attention_heads=16, num_key_value_heads=8,
                            intermediate_size=256, vocab_size=1024,
                            tensor_parallel=tp, tie_word_embeddings=False)
    return cfg


def _paged_logits(model, params, vocab):
    """Logits of a 53-token prompt prefilled in two chunks through the page
    table, then of three greedy decode steps; and the tokens."""
    ps, n_pages = 16, 16
    cache = jax.tree_util.tree_map(
        lambda d: jnp.zeros(d.shape, d.dtype),
        model.paged_cache_defs(n_pages + 1, ps),
        is_leaf=lambda x: hasattr(x, "axes"))
    row = np.arange(1, n_pages + 1, dtype=np.int32)
    rng = np.random.default_rng(0)
    prompt = rng.integers(259, vocab, 53).tolist()
    # prefill in two chunks (a chunk boundary), then three decode steps
    got = []
    for lo, hi in ((0, 32), (32, 53)):
        toks = np.zeros((1, 32), np.int32)
        toks[0, :hi - lo] = prompt[lo:hi]
        lg, cache = model.prefill_paged(
            params, cache, jnp.asarray(toks), jnp.int32(hi - lo),
            jnp.int32(lo), jnp.asarray(row), page_size=ps)
    got.append(np.asarray(lg[0]))
    seq = list(prompt)
    for _ in range(3):
        tok = int(np.argmax(got[-1]))
        seq.append(tok)
        pos = len(seq) - 1
        lg, cache = model.decode_step_paged(
            params, cache, jnp.asarray([[tok]], jnp.int32),
            jnp.asarray([pos], jnp.int32), jnp.asarray(row[None]),
            page_size=ps)
        got.append(np.asarray(lg[0]))
    return got, seq


# the program in float32 differs from the reference only in summation
# order; in bfloat16 (weights, activations, KV and logits, as served) by
# rounding: 0.078 of the logits' spread at two toy layers here, 0.08-0.11
# between two bf16 paths at 24 published layers on a v5e (PERF.md, PR 11).
# A misplaced key, position or page moves them by a whole spread.
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4),
                                       ("bfloat16", 0.15)])
def test_reference_matches_paged_prefill_and_decode(dtype, tol):
    from repro.models.api import build_model
    config = _model()
    cfg, dims = model_config(config)
    cfg = dataclasses.replace(cfg, dtype=dtype)
    model = build_model(cfg, max_seq=256)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(cfg.activation_dtype),
        weights.program_params(SEED, dims, config["init"]))
    with jax.default_matmul_precision("highest"):
        got, seq = _paged_logits(model, params, dims["vocab"])
    states, top = reference.final_states(SEED, config["model"],
                                         config["init"], [seq])
    ref = np.asarray(reference.logits_at(
        states[0], top, list(range(52, 52 + len(got)))))
    err = np.abs(np.stack(got) - ref).max() / ref.std()
    assert err < tol, err


def test_tensor_parallel_shares_add_up_to_the_layer():
    config = _model(tp=8)
    full = dict(config["model"], tensor_parallel=1)
    dims = reference.model_dims(full)
    w = reference.f32_layer_weights(weights.base_key(SEED), 0, dims,
                                    config["init"], None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, dims["d"]))
    eps, theta = 1e-6, 1e6
    with jax.default_matmul_precision("highest"):
        att = reference.attention(x, w, eps, theta)
        x2 = x + att
        f = reference.ffn(x2, w, eps)
        H, KV, ff = dims["heads"], dims["kv_heads"], dims["ffn"]
        h, kv, c = H // 8, KV // 8, ff // 8
        parts_a, parts_f = [], []
        for s in range(8):
            ws = dict(w)
            ws.update(wq=w["wq"][:, s * h:(s + 1) * h],
                      bq=w["bq"][s * h:(s + 1) * h],
                      wk=w["wk"][:, s * kv:(s + 1) * kv],
                      bk=w["bk"][s * kv:(s + 1) * kv],
                      wv=w["wv"][:, s * kv:(s + 1) * kv],
                      bv=w["bv"][s * kv:(s + 1) * kv],
                      wo=w["wo"][s * h:(s + 1) * h],
                      wi_gate=w["wi_gate"][:, s * c:(s + 1) * c],
                      wi_up=w["wi_up"][:, s * c:(s + 1) * c],
                      w_down=w["w_down"][s * c:(s + 1) * c])
            parts_a.append(reference.attention(x, ws, eps, theta))
            parts_f.append(reference.ffn(x2, ws, eps))
    # float32 sums in another order: a few ulps of the layer's output
    np.testing.assert_allclose(sum(parts_a), att, rtol=0, atol=1e-4)
    np.testing.assert_allclose(sum(parts_f), f, rtol=0, atol=1e-4)


def test_share_dims_divide_the_published_sizes():
    d = reference.model_dims({
        "num_hidden_layers": 40, "hidden_size": 8192,
        "num_attention_heads": 64, "num_key_value_heads": 8,
        "intermediate_size": 29568, "vocab_size": 152064,
        "tie_word_embeddings": False, "tensor_parallel": 8})
    assert (d["heads"], d["kv_heads"], d["ffn"], d["vocab"],
            d["head_dim"]) == (8, 1, 3696, 19008, 128)


def test_weights_are_the_same_per_layer_and_stacked():
    config = _model()
    dims = reference.model_dims(config["model"])
    stacked = weights.program_params(SEED, dims, config["init"])
    one = reference.f32_layer_weights(weights.base_key(SEED), 1, dims,
                                      config["init"], None)
    np.testing.assert_array_equal(
        np.asarray(stacked["blocks"]["ffn"]["wo"][1], np.float32),
        np.asarray(one["w_down"]))
    np.testing.assert_array_equal(
        np.asarray(stacked["blocks"]["attn"]["wq"][1], np.float32),
        np.asarray(one["wq"]))


def test_int8_roundtrip_error_is_half_a_step():
    w = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
    q = reference.int8_roundtrip(w, (0,))
    step = jnp.max(jnp.abs(w), axis=0) / 127
    assert bool((jnp.abs(q - w) <= step / 2 + 1e-7).all())
    assert not bool((q == w).all())
