"""The tensor plan of a Falcon-H1 decoder (`FalconH1ForCausalLM`, tiiuae): every
layer a Mamba-2 mixer AND an attention mixer on the same `input_layernorm`,
then a dense SwiGLU under `pre_ff_layernorm`; `final_layernorm` and an untied
head.

`tensor_plan(cfg) -> [(name, shape, kind)]` in file order, HF names and
[out, in] shapes as the builder of PR 45 knew the `falcon_h1` layout
(`model.layers.N.{input_layernorm, pre_ff_layernorm}`, `mamba.{in_proj, conv1d,
dt_bias, A_log, D, norm, out_proj}`, `self_attn.{q,k,v,o}_proj`,
`feed_forward.{gate,up,down}_proj`, `model.final_layernorm`,
`model.embed_tokens`, `lm_head`; no network here to check it;
`reference/falcon_h1.py` and the program's
`models/loader.py:load_falcon_h1_weights` read the same names). The draw of
tensor i is keyed by (seed, i): order and names are part of every checkpoint
this plan ever wrote.

Kinds, tensor by tensor, under the PUBLISHED multipliers (none is changed; the
writer knows `normal`, 0.02 in bfloat16, and `ones`). What each choice leaves
visible to the logprob comparison was measured by the plain reference against
itself at full width, 4 probes x 8 tokens (PR 45, CPU, seed 2147483999; "reads"
below) and is held at small width in `tests/test_falcon_h1.py`
(`test_the_plan_under_the_published_multipliers_*`: the flat logits, and
`k_proj`, `A_log` and the taps against the writer's other kind; `mamba.D`'s
share of y turns on the widths and was measured at full width alone).

- The scale everything is read in. `lm_head.weight` `normal` and a unit-rms
  normed hidden state give logits 0.02 x sqrt(5120) x 2^-7 = 0.011 wide: every
  logprob over 261120 rows is -12.47 give or take a few hundredths, and a
  fault that moves the hidden state by a share s moves a logprob by about
  s x 0.011. The comparison lives in the thousandths.
- `model.embed_tokens.weight` `normal`: the residual starts 0.02 x 5.66 = 0.113
  an element.
- Every norm weight (`*layernorm.weight`, `mamba.norm.weight`) `ones`.
- `mamba.in_proj.weight` `normal`: 0.02 x sqrt(5120) x 0.25 = 0.358 before `m`;
  after it z 0.127, x 0.089, B 0.063, C 0.179, dt 0.127 wide.
- `mamba.conv1d.weight` `ones`, as NemotronH's plan: a box filter over the last
  4 inputs, so three quarters of a decode step's convolution come from the
  window the prefill left (a window late by one token reads 0.0102). At 0.02
  x, B and C would be 0.002-0.007 wide and the state nothing.
  `mamba.conv1d.bias` and `mamba.dt_bias` `normal`: dt = softplus(0.13 n +
  0.02 n) is 0.69 give or take 0.07.
- `mamba.A_log` `normal`, NOT `ones`: A = -exp(0.02 n) is -1, so a token
  keeps exp(-0.69) = 0.50 of the state, and about half of what `S C` reads
  was written before the token. `ones` (A = -e) keeps 0.15 a token.
- `mamba.D` `normal`, NOT `ones`: `y = S C + D x` with x 0.09 wide and `S C`
  0.013. At D = 1 the skip term is seven times the recurrent one and a state
  LOST at the hand-off from prefill to decode reads 0.0013; at 0.02 the
  recurrent term is all of y (the group norm that follows rescales it) and
  the lost state reads 0.0084 (0.0064 with `k_proj` as below). What this
  gives up: a wrong D moves nothing here.
- `mamba.out_proj.weight` `normal`: 0.02 x sqrt(4096) x 0.0884 = 0.113, as
  large as the residual it is added to: the Mamba branch carries the block
  (dropped, it reads 0.021).
- `self_attn.k_proj.weight` `ones`, NOT `normal`. At 0.02 a key is 1.43 x
  0.011 = 0.016 wide and a score q.k / sqrt(128) 0.023: the softmax is uniform
  over the context, rope turns nothing (left out it reads 0.00004, positions
  off by one from the hand-off on 0.00001), and the branch is a running mean
  of v. With `ones` every lane of a key is `key_multiplier` x the sum of the
  5120 normed inputs, 0.79 wide (one number a token, the same in all 4 kv
  heads), rope turns it by position, and scores are of order one: rope left
  out reads 0.0065, positions off by one 0.0024, `key_multiplier` left out
  0.035 (0.011 before), the attention branch dropped 0.0126 (0.0054
  before). What this gives up: the 512 rows of `k_proj` are one row, so a
  fault that permutes or drops key LANES or kv heads before rope moves
  nothing; after rope the lanes differ by frequency (about 14 of the 64
  turn within 300 positions at theta 1e11).
- `self_attn.{q,v,o}_proj.weight` `normal`: the branch adds 1.43 x 1.01 x
  0.0375 = 0.054 / sqrt(tokens attended) beside Mamba's 0.113.
- `feed_forward.*` `normal`: `mlp_multipliers[1]` 0.011 makes the SwiGLU add
  0.007 an element a layer, a twentieth of the Mamba branch; left out it
  reads 0.036, so the product and both multipliers are seen, but a fault of a
  few percent INSIDE the SwiGLU is not.
"""

from __future__ import annotations

#: tensors that are not `normal`: every norm weight, the convolution's taps and
#: the keys' projection (the docstring says why each)
ONES = ("layernorm.weight", "mamba.norm.weight", "mamba.conv1d.weight", "self_attn.k_proj.weight")


def tensor_plan(cfg: dict) -> list:
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    G, K = cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    inner, conv_dim = H * P, H * P + 2 * G * N
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    plan = [("model.embed_tokens.weight", (V, D)),
            ("model.final_layernorm.weight", (D,)),
            ("lm_head.weight", (V, D))]
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        plan += [
            (p + "input_layernorm.weight", (D,)),
            (p + "mamba.in_proj.weight", (inner + conv_dim + H, D)),
            (p + "mamba.conv1d.weight", (conv_dim, 1, K)),
            (p + "mamba.conv1d.bias", (conv_dim,)),
            (p + "mamba.dt_bias", (H,)),
            (p + "mamba.A_log", (H,)),
            (p + "mamba.D", (H,)),
            (p + "mamba.norm.weight", (inner,)),
            (p + "mamba.out_proj.weight", (D, inner)),
            (p + "self_attn.q_proj.weight", (Hq * hd, D)),
            (p + "self_attn.k_proj.weight", (Hkv * hd, D)),
            (p + "self_attn.v_proj.weight", (Hkv * hd, D)),
            (p + "self_attn.o_proj.weight", (D, Hq * hd)),
            (p + "pre_ff_layernorm.weight", (D,)),
            (p + "feed_forward.gate_proj.weight", (F, D)),
            (p + "feed_forward.up_proj.weight", (F, D)),
            (p + "feed_forward.down_proj.weight", (D, F)),
        ]
    return [(name, shape, "ones" if name.endswith(ONES) else "normal") for name, shape in plan]
