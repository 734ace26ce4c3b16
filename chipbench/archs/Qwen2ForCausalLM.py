"""Dense Qwen2 decoder (arXiv:2407.10671): grouped-query attention with
q/k/v biases, a SwiGLU FFN, RMSNorm and RoPE over head halves. The
architecture of every configuration whose ``model.architectures[0]`` is
``Qwen2ForCausalLM``; ``chipbench.run.arch`` loads it by that name.

Its pieces live in the modules they were written in: the weight layout in
``chipbench/weights.py``, the float32 reference in
``chipbench/reference.py`` and the yardstick in ``chipbench/flops.py``.
"""
from chipbench.flops import (  # noqa: F401
    append_attention, decode_attention, kv_bytes_per_token,
    layer_matmul_params, model_step)
from chipbench.reference import final_states  # noqa: F401
from chipbench.reference import model_dims as dims  # noqa: F401
from chipbench.weights import program_params  # noqa: F401


def program_config(config: dict, dims: dict):
    """The program's ``ModelConfig`` of a configuration, at the sizes this
    chip holds (``dims``)."""
    from repro.configs.base import ModelConfig
    m = config["model"]
    return ModelConfig(
        arch_id=config["name"], family="dense", n_layers=dims["layers"],
        d_model=dims["d"], n_heads=dims["heads"],
        n_kv_heads=dims["kv_heads"], d_ff=dims["ffn"], vocab=dims["vocab"],
        head_dim=dims["head_dim"], qkv_bias=bool(m["qkv_bias"]),
        rope_theta=float(m["rope_theta"]), rms_eps=float(m["rms_norm_eps"]),
        tie_embeddings=dims["tied"], dtype=m["torch_dtype"], remat=False,
        source=config["source"])
