"""Operations and bytes that the published architecture needs, counted
from a configuration file, for the utilisation and roofline metrics.

Model FLOPs of a token are 2 x the parameters its forward pass multiplies
by (attention projections, the router, the top-k routed and the shared
experts, and the LM head where the token's logits are needed), plus
attention's score and value products over its causal context.  Work the
program adds beyond that (the dense one-chip MoE oracle, tokens repeated
over a mesh, logits nobody reads) does not count.
"""
from __future__ import annotations


def _attention_params(c: dict) -> int:
    D, H = c["hidden_size"], c["num_attention_heads"]
    if c.get("kv_lora_rank"):                       # latent attention (MLA)
        qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
        q = (D * c["q_lora_rank"] + c["q_lora_rank"] * H * qk
             if c.get("q_lora_rank") else D * H * qk)
        kv_a = D * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
        kv_b = c["kv_lora_rank"] * H * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
        return q + kv_a + kv_b + H * c["v_head_dim"] * D
    hd = c.get("head_dim") or D // H
    return D * H * hd * 2 + 2 * D * c["num_key_value_heads"] * hd


def _score_flops_per_key(c: dict) -> int:
    """FLOPs per (query, key) pair of one layer: q.k and p.v over heads."""
    H = c["num_attention_heads"]
    if c.get("kv_lora_rank"):
        qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
        return 2 * H * (qk + c["v_head_dim"])
    hd = c.get("head_dim") or c["hidden_size"] // H
    return 4 * H * hd


def _experts(c: dict) -> int:
    return c.get("n_routed_experts", c.get("num_experts"))


def _ffn_params(c: dict) -> int:
    """Routed top-k, shared experts and router of one MoE layer."""
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    n = c["num_experts_per_tok"] * 3 * D * F + D * _experts(c)
    if c.get("n_shared_experts"):
        n += c["n_shared_experts"] * 3 * D * F
    if c.get("shared_expert_intermediate_size"):
        n += 3 * D * c["shared_expert_intermediate_size"] + D
    return n


def layer_params(c: dict) -> int:
    """Parameters one token multiplies by in one layer."""
    return _attention_params(c) + _ffn_params(c)


def round_flops(c: dict, prompt: int, gen: int, batch: int) -> float:
    """Model FLOPs of one closed-loop round: each request feeds ``prompt``
    + ``gen`` - 1 tokens (the last served token is never fed back) and
    reads ``gen`` rows of logits."""
    L = c["num_hidden_layers"]
    fed = prompt + gen - 1
    # causal contexts 1 .. fed
    keys = fed * (fed + 1) / 2
    per_request = (2.0 * L * layer_params(c) * fed
                   + L * _score_flops_per_key(c) * keys
                   + 2.0 * c["hidden_size"] * c["vocab_size"] * gen)
    return batch * per_request

