"""The tensor plan of a DeepSeek-V2-style decoder: multi-head latent
attention, leading dense layers, then layers of routed experts beside shared
ones. A test fixture (`benchmark/tests/test_rehearsal_mla_moe.py` lays it into
a temporary `--root`), and the worked example of what a configuration of
another architecture brings: this file, its plain reference and its data.

`tensor_plan(cfg) -> [(name, shape, kind)]` in file order, HF names and
[out, in] shapes as `transformers` `DeepseekV2ForCausalLM` has them (and as
the program's `models/loader.py:load_deepseek_weights` reads them); kind is
"normal" or "ones". The draw of tensor i is keyed by (seed, i): order and
names are part of every checkpoint this plan ever wrote.
"""

from __future__ import annotations


def tensor_plan(cfg: dict) -> list:
    D, V, H = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    dc, ql = cfg["kv_lora_rank"], cfg.get("q_lora_rank")
    I, Fm, E = cfg["intermediate_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    Fs = Fm * cfg["n_shared_experts"]
    plan = [("model.embed_tokens.weight", (V, D), "normal"),
            ("model.norm.weight", (D,), "ones")]
    if not cfg.get("tie_word_embeddings", False):
        plan.append(("lm_head.weight", (V, D), "normal"))

    def swiglu(prefix: str, width: int) -> list:
        return [(prefix + "gate_proj.weight", (width, D), "normal"),
                (prefix + "up_proj.weight", (width, D), "normal"),
                (prefix + "down_proj.weight", (D, width), "normal")]

    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        plan.append((p + "input_layernorm.weight", (D,), "ones"))
        if ql:
            plan += [(p + "self_attn.q_a_proj.weight", (ql, D), "normal"),
                     (p + "self_attn.q_a_layernorm.weight", (ql,), "ones"),
                     (p + "self_attn.q_b_proj.weight", (H * (dn + dr), ql), "normal")]
        else:
            plan.append((p + "self_attn.q_proj.weight", (H * (dn + dr), D), "normal"))
        plan += [
            (p + "self_attn.kv_a_proj_with_mqa.weight", (dc + dr, D), "normal"),
            (p + "self_attn.kv_a_layernorm.weight", (dc,), "ones"),
            (p + "self_attn.kv_b_proj.weight", (H * (dn + dv), dc), "normal"),
            (p + "self_attn.o_proj.weight", (D, H * dv), "normal"),
            (p + "post_attention_layernorm.weight", (D,), "ones"),
        ]
        if l < cfg.get("first_k_dense_replace", 0):
            plan += swiglu(p + "mlp.", I)
            continue
        plan.append((p + "mlp.gate.weight", (E, D), "normal"))  # the router
        for e in range(E):
            plan += swiglu(p + f"mlp.experts.{e}.", Fm)
        plan += swiglu(p + "mlp.shared_experts.", Fs)
    return plan
