"""Operations and bytes the algorithm needs, from shapes and token counts:
the yardstick of the dense Qwen2 architecture
(``chipbench/archs/Qwen2ForCausalLM.py``).

These count the work, not what an implementation walks: attention over the
live tokens of each row (not ``max_seq`` pages), each needed key and value
read once, no padding. A faster kernel that skips dead pages then reads as
a higher share of the roofline, not as less work. FLOPs count a multiply
and an add as two. ``dims`` is :func:`chipbench.reference.model_dims`.
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(dims: dict) -> int:
    """Weights each token multiplies in one layer: q, k, v, o and FFN."""
    d, H, KV, hd, ff = (dims["d"], dims["heads"], dims["kv_heads"],
                        dims["head_dim"], dims["ffn"])
    return d * (H + 2 * KV) * hd + H * hd * d + 3 * d * ff


def kv_bytes_per_token(dims: dict) -> int:
    """Key and value bytes of one token over all layers (bf16)."""
    return 2 * dims["kv_heads"] * dims["head_dim"] * BF16 * dims["layers"]


def decode_attention(dims: dict, rows: int, live_tokens: float):
    """(flops, bytes) of paged decode attention for ``rows`` query rows
    whose live contexts sum to ``live_tokens`` (each row counts the token
    it writes): q.k and p.v per live key, every live key and value read
    once, q read and the output written once per row."""
    H, hd, L = dims["heads"], dims["head_dim"], dims["layers"]
    flops = 4.0 * H * hd * live_tokens * L
    qo = 2.0 * rows * H * hd * BF16 * L
    return flops, live_tokens * kv_bytes_per_token(dims) + qo


def append_attention(dims: dict, computed: float, attended: float,
                     context_tokens: float):
    """(flops, bytes) of paged append (prefill) attention: ``computed``
    query tokens, which together attend ``attended`` keys (token at
    position p attends p + 1), over requests whose contexts (prefix plus
    suffix) total ``context_tokens``, each read once."""
    H, hd, L = dims["heads"], dims["head_dim"], dims["layers"]
    flops = 4.0 * H * hd * attended * L
    qo = 2.0 * computed * H * hd * BF16 * L
    return flops, context_tokens * kv_bytes_per_token(dims) + qo


def model_step(dims: dict, tokens: float, logit_rows: float,
               attention_flops: float) -> float:
    """Model FLOPs of ``tokens`` computed tokens (prefill tokens not served
    from cache plus decode tokens): 2 per weight per token in every layer,
    the attention over live context, and the head for ``logit_rows``."""
    per_token = 2.0 * layer_matmul_params(dims) * dims["layers"]
    head = 2.0 * dims["d"] * dims["vocab"] * logit_rows
    return per_token * tokens + head + attention_flops
