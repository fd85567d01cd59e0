"""The tensor plan of a Cohere2-MoE decoder (`Cohere2MoeForCausalLM`, Command
A+): every layer a parallel block of attention and an expert layer under one
LayerNorm; a tied embedding (no second head in the file) and a final norm.

`tensor_plan(cfg) -> [(name, shape, kind)]` in file order, HF names and
[out, in] shapes: Cohere2's layout (`model.layers.N.input_layernorm`,
`self_attn.{q,k,v,o}_proj`) with an expert layer in DeepSeek's style
(`mlp.gate`, `mlp.experts.E.{gate,up,down}_proj`, `mlp.shared_experts.J.*`),
as the builder of PR 37 chose them with no network to check the published
names; `reference/cohere2_moe.py` and the program's
`models/loader.py:load_cohere2_moe_weights` read the same names. The draw of
tensor i is keyed by (seed, i): order and names are part of every checkpoint
this plan ever wrote.

Where the configuration is one chip's share of an expert-parallel deployment,
`num_experts` counts the experts HELD (written as `experts.0 ..`), the router
keeps the `moe_routed_over` outputs it scores, and `vocab_size` is the slice
of the vocabulary held. Norm weights are `ones`, the rest `normal` (0.02).
"""

from __future__ import annotations


def tensor_plan(cfg: dict) -> list:
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    held = cfg["num_experts"]
    routed = cfg.get("moe_routed_over", held)
    plan = [("model.embed_tokens.weight", (V, D), "normal"),
            ("model.norm.weight", (D,), "ones")]
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        plan += [
            (p + "input_layernorm.weight", (D,), "ones"),
            (p + "self_attn.q_proj.weight", (Hq * hd, D), "normal"),
            (p + "self_attn.k_proj.weight", (Hkv * hd, D), "normal"),
            (p + "self_attn.v_proj.weight", (Hkv * hd, D), "normal"),
            (p + "self_attn.o_proj.weight", (D, Hq * hd), "normal"),
            (p + "mlp.gate.weight", (routed, D), "normal"),
        ]
        for kind, n in (("experts", held), ("shared_experts", cfg["num_shared_experts"])):
            for e in range(n):
                plan += [(p + f"mlp.{kind}.{e}.gate_proj.weight", (F, D), "normal"),
                         (p + f"mlp.{kind}.{e}.up_proj.weight", (F, D), "normal"),
                         (p + f"mlp.{kind}.{e}.down_proj.weight", (D, F), "normal")]
    return plan
