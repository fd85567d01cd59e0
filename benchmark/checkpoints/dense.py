"""The tensor plan of a dense Llama/Qwen2 decoder (`benchmark.checkpoint`:
`dense`, which is also what a configuration gets that names none).

A plan is `tensor_plan(cfg) -> [(name, shape, kind)]` in file order, with HF
names as the program's loader reads them; kind is "normal" or "ones". The
draw of tensor i is keyed by (seed, i) (`checkpoint.tensor_values`), so a
plan's order and names are part of every checkpoint it ever wrote: another
architecture is another file here, not an edit to this one.

Moved as it was from `checkpoint.py` (PR 27): embedding, final norm, an
untied head where the config says so, then per layer the two norms,
q/k/v/o projections, qkv biases (`attention_bias`, on by default for a
`qwen*` model_type), and the SwiGLU MLP.
"""

from __future__ import annotations


def tensor_plan(cfg: dict) -> list:
    """[(name, shape, kind)] in file order; kind is "normal" or "ones"."""
    D, I, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    plan = [("model.embed_tokens.weight", (V, D), "normal"),
            ("model.norm.weight", (D,), "ones")]
    if not cfg.get("tie_word_embeddings", False):
        plan.append(("lm_head.weight", (V, D), "normal"))
    qwen = "qwen" in str(cfg.get("model_type", "")).lower()
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        plan += [
            (p + "input_layernorm.weight", (D,), "ones"),
            (p + "post_attention_layernorm.weight", (D,), "ones"),
            (p + "self_attn.q_proj.weight", (Hq * hd, D), "normal"),
            (p + "self_attn.k_proj.weight", (Hkv * hd, D), "normal"),
            (p + "self_attn.v_proj.weight", (Hkv * hd, D), "normal"),
            (p + "self_attn.o_proj.weight", (D, Hq * hd), "normal"),
        ]
        if cfg.get("attention_bias", qwen):
            plan += [
                (p + "self_attn.q_proj.bias", (Hq * hd,), "normal"),
                (p + "self_attn.k_proj.bias", (Hkv * hd,), "normal"),
                (p + "self_attn.v_proj.bias", (Hkv * hd,), "normal"),
            ]
        plan += [
            (p + "mlp.gate_proj.weight", (I, D), "normal"),
            (p + "mlp.up_proj.weight", (I, D), "normal"),
            (p + "mlp.down_proj.weight", (D, I), "normal"),
        ]
    return plan
