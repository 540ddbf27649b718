"""The llama architecture, as the harness needs to know it.

A decoder of dense blocks: RMSNorm, rotary grouped-query attention,
RMSNorm and a SwiGLU MLP, each with a residual, between a token
embedding and a final RMSNorm and head. The program serves it as its
``dense`` family; `reference.llama` is its plain reference.
"""
from __future__ import annotations

from chipbench.harness import Refused


def program_config(spec: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig
    if spec["attention_bias"] or spec["mlp_bias"]:
        raise Refused(2, f"{spec['name']}: the served model has no biases")
    return ModelConfig(
        name=spec["name"], family="dense",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        head_dim=spec["head_dim"], rope_theta=float(spec["rope_theta"]),
        norm_eps=spec["rms_norm_eps"],
        tie_embeddings=spec["tie_word_embeddings"],
        dtype=spec["torch_dtype"], param_dtype=spec["torch_dtype"])


def shapes(spec: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, by the checkpoint layout the program reads
    (``layers.attn.wq`` is the stacked (L, D, H*hd) query projection)."""
    L = spec["num_hidden_layers"]
    D = spec["hidden_size"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, F, V = spec["head_dim"], spec["intermediate_size"], spec["vocab_size"]
    out = {
        "embed": (V, D),
        "final_norm": (D,),
        "layers.attn.wk": (L, D, K * hd),
        "layers.attn.wo": (L, H * hd, D),
        "layers.attn.wq": (L, D, H * hd),
        "layers.attn.wv": (L, D, K * hd),
        "layers.ln1": (L, D),
        "layers.ln2": (L, D),
        "layers.mlp.w_down": (L, F, D),
        "layers.mlp.w_gate": (L, D, F),
        "layers.mlp.w_up": (L, D, F),
    }
    if not spec["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    return out


def is_norm(name: str, shape: tuple[int, ...]) -> bool:
    """``final_norm``, ``layers.ln1`` and ``layers.ln2`` are norm scales."""
    return len(shape) == 1 or name.endswith(("ln1", "ln2"))


def layer_matmul_params(spec: dict) -> int:
    D = spec["hidden_size"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, F = spec["head_dim"], spec["intermediate_size"]
    return 2 * D * H * hd + 2 * D * K * hd + 3 * D * F


def prefill_flops(spec: dict, batch: int, seq: int) -> dict:
    """Operations of one prefill step, as multiply-adds times two:

    * ``layers``: the layers' matrix products, for every position of
      every row: 2 x (query, key, value and output projections + the
      three MLP matrices) per token per layer;
    * ``attention``: causal attention, scores and values, for the
      positions a query may see: 4 x B x S^2/2 x heads x head_dim per
      layer, as `models/flops.py` of the program counts it;
    * ``head``: the head, at the last position of each row only, which
      is all the served step computes.

    The embedding lookup and the norms are not matrix products and count
    nothing.
    """
    L = spec["num_hidden_layers"]
    H, hd = spec["num_attention_heads"], spec["head_dim"]
    layers = 2.0 * L * layer_matmul_params(spec) * batch * seq
    attention = L * 4.0 * batch * (seq * seq / 2) * H * hd
    head = 2.0 * batch * spec["hidden_size"] * spec["vocab_size"]
    return {"layers": layers, "attention": attention, "head": head,
            "total": layers + attention + head}
