"""The qwen3_moe architecture, as the harness needs to know it.

A decoder of sparse-expert blocks: RMSNorm, rotary grouped-query
attention with a per-head RMSNorm on the queries and keys before the
rotation, RMSNorm and an expert layer, each with a residual, between a
token embedding and a final RMSNorm and head. The expert layer's router
scores every token against all the published experts (the configuration's
``published.num_experts``), keeps the top ``num_experts_per_tok`` with
their probabilities renormalised, and the layer computes the SwiGLU of
the experts this chip holds: ``num_experts`` of them from
``first_expert``, each ``moe_intermediate_size`` wide. What the experts
held elsewhere would add is left out. The program serves it as its
``moe`` family; `reference.qwen3_moe` is its plain reference.
"""
from __future__ import annotations

import dataclasses

from chipbench.harness import Refused


def router_width(spec: dict) -> int:
    """How many experts the router scores: the published count."""
    return spec["published"]["num_experts"]


def program_config(spec: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig
    if "held_experts" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        raise Refused(2, f"{spec['name']}: the program's expert layer "
                         f"cannot hold a share of the experts")
    unserved = {"attention_bias": True, "norm_topk_prob": False,
                "use_sliding_window": True}
    for key, value in unserved.items():
        if spec[key] == value:
            raise Refused(2, f"{spec['name']}: the served model has no "
                             f"{key} {value}")
    if spec["decoder_sparse_step"] != 1 or spec["mlp_only_layers"]:
        raise Refused(2, f"{spec['name']}: the served model has no dense "
                         f"layers between its expert layers")
    return ModelConfig(
        name=spec["name"], family="moe",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["moe_intermediate_size"], vocab_size=spec["vocab_size"],
        head_dim=spec["head_dim"], qk_norm=True,
        rope_theta=float(spec["rope_theta"]),
        num_experts=router_width(spec),
        num_experts_per_tok=spec["num_experts_per_tok"],
        held_experts=spec["num_experts"], first_expert=spec["first_expert"],
        router_dtype=spec["torch_dtype"], norm_eps=spec["rms_norm_eps"],
        tie_embeddings=spec["tie_word_embeddings"],
        dtype=spec["torch_dtype"], param_dtype=spec["torch_dtype"])


def shapes(spec: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, by the checkpoint layout the program reads
    (``layers.moe.w_gate`` is the stacked (L, held, D, F) gate projection
    of the held experts; ``layers.moe.router`` (L, D, E) scores all E)."""
    L = spec["num_hidden_layers"]
    D = spec["hidden_size"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, V = spec["head_dim"], spec["vocab_size"]
    n, F = spec["num_experts"], spec["moe_intermediate_size"]
    out = {
        "embed": (V, D),
        "final_norm": (D,),
        "layers.attn.k_norm": (L, hd),
        "layers.attn.q_norm": (L, hd),
        "layers.attn.wk": (L, D, K * hd),
        "layers.attn.wo": (L, H * hd, D),
        "layers.attn.wq": (L, D, H * hd),
        "layers.attn.wv": (L, D, K * hd),
        "layers.ln1": (L, D),
        "layers.ln2": (L, D),
        "layers.moe.router": (L, D, router_width(spec)),
        "layers.moe.w_down": (L, n, F, D),
        "layers.moe.w_gate": (L, n, D, F),
        "layers.moe.w_up": (L, n, D, F),
    }
    if not spec["tie_word_embeddings"]:
        out["lm_head"] = (D, V)
    return out


def is_norm(name: str, shape: tuple[int, ...]) -> bool:
    """``final_norm``, ``layers.ln1``, ``layers.ln2`` and the per-head
    ``q_norm`` and ``k_norm`` are norm scales."""
    return name.endswith(("final_norm", "ln1", "ln2", "q_norm", "k_norm"))


def prefill_flops(spec: dict, batch: int, seq: int) -> dict:
    """Operations of one prefill step, as multiply-adds times two:

    * ``layers``: per token per layer, 2 x (the query, key, value and
      output projections + the router's D x E scores + the three SwiGLU
      matrices of k x held/E experts). The last term is an expectation:
      of a token's k experts, held/E lie here on average (1 of 8 for 16
      of 128); the tokens a step really routes here vary with the
      routing (``expert_imbalance`` reads the spread);
    * ``attention``: causal attention, scores and values, for the
      positions a query may see: 4 x B x S^2/2 x heads x head_dim per
      layer, as `arch.llama` counts it;
    * ``head``: the head, at the last position of each row only.

    The embedding lookup, norms, softmax, top-k, sort and gather are not
    matrix products and count nothing.
    """
    L, D = spec["num_hidden_layers"], spec["hidden_size"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, F = spec["head_dim"], spec["moe_intermediate_size"]
    E, k, n = router_width(spec), spec["num_experts_per_tok"], spec["num_experts"]
    per_token = (2 * D * H * hd + 2 * D * K * hd + D * E
                 + 3 * D * F * k * n / E)
    layers = 2.0 * L * per_token * batch * seq
    attention = L * 4.0 * batch * (seq * seq / 2) * H * hd
    head = 2.0 * batch * D * spec["vocab_size"]
    return {"layers": layers, "attention": attention, "head": head,
            "total": layers + attention + head}
