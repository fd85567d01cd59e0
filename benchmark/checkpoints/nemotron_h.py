"""The tensor plan of a NemotronH decoder (`NemotronHForCausalLM`): one mixer a
block by `hybrid_override_pattern`: `M` Mamba-2, `*` attention, `E` latent
experts with a shared expert.

`tensor_plan(cfg) -> [(name, shape, kind)]` in file order, HF names and
[out, in] shapes as the builder of PR 29 knew the `nemotron_h` layout (no
network here to check it; `reference/nemotron_h.py` and the program's
`models/loader.py:load_nemotron_h_weights` read the same names). The draw of
tensor i is keyed by (seed, i): order and names are part of every checkpoint
this plan ever wrote.

Where the configuration is one chip's share of an expert-parallel deployment,
`n_routed_experts` counts the experts HELD (written as `experts.0 ..`), the
router keeps the `moe_routed_over` outputs it scores, and `vocab_size` is the
slice of the vocabulary held.

Kinds. The writer knows `normal` (0.02) and `ones`. `A_log`, `D`, every norm
weight and `conv1d.weight` are `ones`, so A = -e and the convolution is a box
filter over the last `conv_kernel` inputs; `dt_bias`, the convolution's bias
and `e_score_correction_bias` are `normal`. With `W_in` at 0.02 over a width of
4096 the pre-activation of dt is about N(0, 1.3): dt = softplus of that has a
median of 0.69 and the decay exp(-e dt) a token a median of 0.15, above 0.6 for
about a tenth of the draws, so a state matters for the next few tokens and the
first decoded tokens lean on what the prefill left. The convolution is not
`normal`: at 0.02 its output, and with it x, B and C, would be ~0.03 and the
recurrent term a few percent of `D x`; a lost hand-off state then moves the
logprobs by 0.004 and no comparison sees it (`tests/test_nemotron_h.py`
measures both).
"""

from __future__ import annotations


def tensor_plan(cfg: dict) -> list:
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    G, K = cfg["n_groups"], cfg["conv_kernel"]
    inner, conv_dim = H * P, H * P + 2 * G * N
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    held = cfg["n_routed_experts"]
    routed = cfg.get("moe_routed_over", held)
    Z, F = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    Fs = cfg["moe_shared_expert_intermediate_size"]
    plan = [("backbone.embeddings.weight", (V, D), "normal"),
            ("backbone.norm_f.weight", (D,), "ones"),
            ("lm_head.weight", (V, D), "normal")]
    for l, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = f"backbone.layers.{l}."
        plan.append((p + "norm.weight", (D,), "ones"))
        m = p + "mixer."
        if kind == "M":
            plan += [
                (m + "in_proj.weight", (inner + conv_dim + H, D), "normal"),
                (m + "conv1d.weight", (conv_dim, 1, K), "ones"),
                (m + "conv1d.bias", (conv_dim,), "normal"),
                (m + "dt_bias", (H,), "normal"),
                (m + "A_log", (H,), "ones"),
                (m + "D", (H,), "ones"),
                (m + "norm.weight", (inner,), "ones"),
                (m + "out_proj.weight", (D, inner), "normal"),
            ]
        elif kind == "*":
            plan += [
                (m + "q_proj.weight", (Hq * hd, D), "normal"),
                (m + "k_proj.weight", (Hkv * hd, D), "normal"),
                (m + "v_proj.weight", (Hkv * hd, D), "normal"),
                (m + "o_proj.weight", (D, Hq * hd), "normal"),
            ]
        elif kind == "E":
            plan += [
                (m + "gate.weight", (routed, D), "normal"),
                (m + "gate.e_score_correction_bias", (routed,), "normal"),
                (m + "fc1_latent_proj.weight", (Z, D), "normal"),
                (m + "fc2_latent_proj.weight", (D, Z), "normal"),
            ]
            for e in range(held):
                plan += [(m + f"experts.{e}.up_proj.weight", (F, Z), "normal"),
                         (m + f"experts.{e}.down_proj.weight", (Z, F), "normal")]
            plan += [(m + "shared_experts.up_proj.weight", (Fs, D), "normal"),
                     (m + "shared_experts.down_proj.weight", (D, Fs), "normal")]
        else:
            raise ValueError(f"block {l}: unknown kind {kind!r} in hybrid_override_pattern")
    return plan
