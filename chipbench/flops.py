"""Operations one prefill step of a llama-architecture decoder needs.

Counted from the configuration file and the step's shape alone, as
multiply-adds times two:

* the layers' matrix products, for every position of every row:
  2 x (query, key, value and output projections + the three MLP
  matrices) per token per layer;
* causal attention, scores and values, for the positions a query may
  see: 4 x B x S^2/2 x heads x head_dim per layer, as `models/flops.py`
  of the program counts it;
* the head, at the last position of each row only, which is all the
  served step computes.

The embedding lookup and the norms are not matrix products and count
nothing.
"""
from __future__ import annotations


def layer_matmul_params(spec: dict) -> int:
    D = spec["hidden_size"]
    H, K = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, F = spec["head_dim"], spec["intermediate_size"]
    return 2 * D * H * hd + 2 * D * K * hd + 3 * D * F


def prefill_flops(spec: dict, batch: int, seq: int) -> dict:
    L = spec["num_hidden_layers"]
    H, hd = spec["num_attention_heads"], spec["head_dim"]
    layers = 2.0 * L * layer_matmul_params(spec) * batch * seq
    attention = L * 4.0 * batch * (seq * seq / 2) * H * hd
    head = 2.0 * batch * spec["hidden_size"] * spec["vocab_size"]
    return {"layers": layers, "attention": attention, "head": head,
            "total": layers + attention + head}
