"""The tensor plan of an LFM2-MoE decoder (`Lfm2MoeForCausalLM`, LiquidAI
LFM2-8B-A1B): every layer an operator (`layer_types`: a gated short convolution
or attention with a norm per head on q and k) under `operator_norm`, then an
FFN under `ffn_norm` (the first `num_dense_layers` dense, the rest experts
behind a sigmoid router with a selection bias); `embedding_norm` and a tied
embedding (no second head in the file).

`tensor_plan(cfg) -> [(name, shape, kind)]` in file order, HF names and
[out, in] shapes as the builder of PR 42 knew the `lfm2_moe` layout
(`model.layers.N.{operator_norm, ffn_norm}`, `conv.{in_proj, conv, out_proj}`,
`self_attn.{q,k,v,out}_proj`, `self_attn.{q,k}_layernorm`,
`feed_forward.{w1,w2,w3}` or `feed_forward.{gate, expert_bias,
experts.E.{w1,w2,w3}}`; no network here to check it; `reference/lfm2_moe.py`
and the program's `models/loader.py:load_lfm2_moe_weights` read the same
names). The draw of tensor i is keyed by (seed, i): order and names are part
of every checkpoint this plan ever wrote.

Kinds. The writer knows `normal` (0.02) and `ones`. Every norm weight is
`ones`, the two per-head ones included. `conv.conv.weight` is `ones`: the three
taps are a box filter over `B*x` of the last three positions, so two thirds of
a decode step's convolution come from the window the prefill left, in every
one of the conv layers; at 0.02 the taps' output would be ~0.03 of `B*x`, the
operator a few percent of the residual, and a lost window invisible (what
ISSUE 29's first plan found for NemotronH). `expert_bias` is `normal`: 0.02
beside sigmoid scores that lie about 0.03 apart at the fourth rank moves the
choice in a share of the rows and the weights in none.
"""

from __future__ import annotations


def tensor_plan(cfg: dict) -> list:
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    F, Fm = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    held = cfg["num_experts"]
    routed = cfg.get("moe_routed_over", held)
    plan = [("model.embed_tokens.weight", (V, D), "normal"),
            ("model.embedding_norm.weight", (D,), "ones")]
    for l, kind in enumerate(cfg["layer_types"]):
        p = f"model.layers.{l}."
        plan.append((p + "operator_norm.weight", (D,), "ones"))
        if kind == "conv":
            plan += [
                (p + "conv.in_proj.weight", (3 * D, D), "normal"),
                (p + "conv.conv.weight", (D, 1, cfg["conv_L_cache"]), "ones"),
                (p + "conv.out_proj.weight", (D, D), "normal"),
            ]
        elif kind == "full_attention":
            plan += [
                (p + "self_attn.q_proj.weight", (Hq * hd, D), "normal"),
                (p + "self_attn.k_proj.weight", (Hkv * hd, D), "normal"),
                (p + "self_attn.v_proj.weight", (Hkv * hd, D), "normal"),
                (p + "self_attn.out_proj.weight", (D, Hq * hd), "normal"),
                (p + "self_attn.q_layernorm.weight", (hd,), "ones"),
                (p + "self_attn.k_layernorm.weight", (hd,), "ones"),
            ]
        else:
            raise ValueError(f"layer {l}: unknown kind {kind!r} in layer_types")
        plan.append((p + "ffn_norm.weight", (D,), "ones"))
        f = p + "feed_forward."
        if l < cfg["num_dense_layers"]:
            plan += [(f + "w1.weight", (F, D), "normal"), (f + "w3.weight", (F, D), "normal"),
                     (f + "w2.weight", (D, F), "normal")]
        else:
            plan += [(f + "gate.weight", (routed, D), "normal"),
                     (f + "expert_bias", (routed,), "normal")]
            for e in range(held):
                plan += [(f + f"experts.{e}.w1.weight", (Fm, D), "normal"),
                         (f + f"experts.{e}.w3.weight", (Fm, D), "normal"),
                         (f + f"experts.{e}.w2.weight", (D, Fm), "normal")]
    return plan
