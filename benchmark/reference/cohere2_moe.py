"""The plain reference for Cohere2-MoE (`Cohere2MoeForCausalLM`, Command A+):
one forward pass in numpy float32, no cache, no kernel, no batching, nothing
imported from `dynamo_tpu`.

The model, as the configuration's keys are read (ISSUE 37 wrote the reading
out; it reproduces the family's name, 218B in all and 25B a token):

- Tied embedding over `vocab_size` ids, `logit_scale` times the head's output.
  `num_hidden_layers` layers, then a final LayerNorm. LayerNorm is Cohere's:
  mean-centred, divided by `sqrt(var + layer_norm_eps)`, times a weight, no
  bias.
- THE BLOCK IS PARALLEL (`use_parallel_block`): `n = LayerNorm(x)`;
  `x = x + Attn(n) + FFN(n)`. One norm a layer.
- Attention: `num_attention_heads` query and `num_key_value_heads` key-value
  heads of `head_dim`, no bias, no qk-norm, softmax at 1/sqrt(head_dim).
  `layer_types[l]` = `sliding_attention`: rope on q and k by INTERLEAVED PAIRS
  (`position_embedding_type` `rope_gptj`, `rotary_pct` 1, theta `rope_theta`:
  lanes 2i and 2i+1 are a pair turned by position x theta^(-2i/head_dim)), and
  a query at position p sees the keys in (p - `sliding_window`, p], itself
  included. `full_attention`: causal over the whole context, NO positional
  embedding.
- FFN: `s = sigmoid(W_r n)` over the `moe_routed_over` experts; chosen = top
  `num_experts_per_tok` of `s`; `w_k = s_k / sum of the chosen s`
  (`norm_topk_prob`); `routed = sum_k w_k W_down_k (silu(W_gate_k n) * W_up_k
  n)`; `shared = (1 / num_shared_experts) sum_j W_down_j (silu(W_gate_j n) *
  W_up_j n)`; `FFN = routed + shared`.

Departures from the published model, and assumptions:

- THE SHARE. The checkpoint holds `num_experts` experts of the
  `moe_routed_over` the router scores, ids `moe_expert_offset` onwards, and
  `vocab_size` rows of the published vocabulary: one chip's share of an
  expert-parallel deployment. The routed sum runs over the chosen experts that
  are HELD; what the others would add is left out, here as in the program, and
  the weights are still normalised over everything chosen. Where the keys
  `moe_routed_over` / `moe_expert_offset` are absent, all experts are held.
- `intermediate_size` is read as ONE expert's width (the config has no key of
  its own for it: the catalog's note), for routed and shared experts alike.
- `shared_expert_combination_strategy` `average` is read as the mean of the
  shared experts' outputs, added to the routed sum.
- No selection bias: the config has no key for one (`expert_selection_fn`
  `sigmoid` alone).
- The window includes the query itself (the HF mask convention): W keys.
- `first_k_dense_replace` 0: no leading dense layer, so
  `prefix_dense_intermediate_size` and `prefix_dense_sliding_window_pattern`
  are inert keys. `rms_norm_eps` is null: `layer_norm_eps` is the one used.
- Text only: the vision tower is outside the language model's config.
- Tensor names are Cohere2's HF layout with an expert layer in DeepSeek's
  style (`model.layers.N.input_layernorm`, `self_attn.{q,k,v,o}_proj`,
  `mlp.gate`, `mlp.experts.E.{gate,up,down}_proj`, `mlp.shared_experts.J.*`),
  chosen by the builder of PR 37 without a network to check them;
  `benchmark/checkpoints/cohere2_moe.py` writes the same names.

The checkpoint is read one tensor at a time and cast to float32. An expert's
three products run over the rows that chose it; the head is taken on the
compared rows only; attention runs in blocks of query rows over the keys a
block can see, so that a 12k-token probe fits. `options` are the controls of
`benchmark/tests/test_controls_cohere2_moe.py` and `chip_long_probe.py`
(another rope, summed shared experts, no window mask, a zeroed expert, a lower
precision of the matrices); none is set in a benchmark run.

    JAX_PLATFORMS=cpu python benchmark/reference/cohere2_moe.py CKPT PROBES.json OUT.json

PROBES.json: [{"tokens": [prompt ids ..., chosen ids ...], "prompt_len": n}].
OUT.json: [[log p(tokens[i] | tokens[:i]) for i in prompt_len..len-1], ...].
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

SLIDING = "sliding_attention"
#: query rows of one attention block
BLOCK_ROWS = 256


def layer_norm(x, w, eps):
    c = x - x.mean(axis=-1, keepdims=True)
    return c / np.sqrt((c * c).mean(axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + np.exp(-x))


def rope(x, positions, theta: float, by: str = "pairs"):
    """x [L, H, hd]. `pairs`: lanes (2i, 2i+1) turn together (rope_gptj);
    `halves` (a control): lane i with lane i + hd/2."""
    hd = x.shape[-1]
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = positions.astype(np.float32)[:, None] * inv[None]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    if by == "pairs":
        x1, x2 = x[..., 0::2], x[..., 1::2]
        out = np.empty_like(x)
        out[..., 0::2], out[..., 1::2] = x1 * cos - x2 * sin, x2 * cos + x1 * sin
        return out
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(n, lin, prefix: str, cfg: dict, kind: str, options: dict):
    """n [L, D] (normed) of ONE sequence from position 0 -> [L, D].
    `lin(x, name)` is x times the checkpoint's matrix `name`, transposed."""
    L, D = n.shape
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    G = Hq // Hkv
    q = lin(n, prefix + "q_proj.weight").reshape(L, Hq, hd)
    k = lin(n, prefix + "k_proj.weight").reshape(L, Hkv, hd)
    v = lin(n, prefix + "v_proj.weight").reshape(L, Hkv, hd)
    window = 0
    if kind == SLIDING:
        pos = np.arange(L)
        q = rope(q, pos, float(cfg["rope_theta"]), options.get("rope", "pairs"))
        k = rope(k, pos, float(cfg["rope_theta"]), options.get("rope", "pairs"))
        window = int(cfg["sliding_window"]) if options.get("window", True) else 0
    out = np.empty((L, Hq, hd), np.float32)
    scale = np.float32(1.0 / np.sqrt(hd))
    for lo in range(0, L, BLOCK_ROWS):
        hi = min(L, lo + BLOCK_ROWS)
        k_lo = max(0, lo - window + 1) if window else 0
        qp, kp = np.arange(lo, hi)[:, None], np.arange(k_lo, hi)[None, :]
        seen = kp <= qp
        if window:
            seen &= kp > qp - window
        for h in range(Hkv):
            qs = q[lo:hi, h * G:(h + 1) * G].transpose(1, 0, 2)  # [G, rows, hd]
            scores = qs @ k[k_lo:hi, h].T * scale  # [G, rows, keys]
            scores = np.where(seen[None], scores, -np.inf)
            scores -= scores.max(axis=-1, keepdims=True)
            p = np.exp(scores)
            p /= p.sum(axis=-1, keepdims=True)
            out[lo:hi, h * G:(h + 1) * G] = (p @ v[k_lo:hi, h]).transpose(1, 0, 2)
    return lin(out.reshape(L, Hq * hd), prefix + "o_proj.weight")


def ffn(n, lin, prefix: str, cfg: dict, options: dict):
    """n [R, D] -> routed (held experts' part) + shared [R, D]."""
    held = cfg["num_experts"]
    off = cfg.get("moe_expert_offset", 0)
    K = cfg["num_experts_per_tok"]
    s = 1.0 / (1.0 + np.exp(-lin(n, prefix + "gate.weight")))  # [R, routed over]
    idx = np.argsort(-s, axis=-1, kind="stable")[:, :K]
    chosen = np.take_along_axis(s, idx, axis=-1)
    wk = chosen / chosen.sum(axis=-1, keepdims=True)

    def expert(rows, p):
        mid = silu(lin(rows, p + "gate_proj.weight")) * lin(rows, p + "up_proj.weight")
        return lin(mid, p + "down_proj.weight")

    out = np.zeros_like(n)
    for e in range(held):
        hit = idx == off + e
        rows = np.flatnonzero(hit.any(axis=-1))
        if rows.size == 0 or options.get("zero_expert") == e:
            continue  # and its matrices are never read
        weight = (wk[rows] * hit[rows]).sum(axis=-1, keepdims=True)
        out[rows] += weight * expert(n[rows], f"{prefix}experts.{e}.")
    J = cfg["num_shared_experts"]
    shared = sum(expert(n, f"{prefix}shared_experts.{j}.") for j in range(J))
    return out + (shared if options.get("shared") == "sum" else shared / J)


def lower_precision(t: np.ndarray, kind: str) -> np.ndarray:
    """A [rows, in] array as it would be held in `kind`, one scale per row (a
    matrix's output channel, an activation's token): `fp8` float8 e4m3 (the
    nearest floating precision below bfloat16), `int8` symmetric. Back in
    float32."""
    import ml_dtypes

    amax = np.maximum(np.abs(t).max(axis=-1, keepdims=True), 1e-30)
    if kind == "fp8":
        scale = amax / 448.0
        return (t / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale
    scale = amax / 127.0
    return np.round(t / scale).clip(-127, 127) * scale


class Weights:
    """The checkpoint's tensors by name, float32, read when asked for; and
    `lin`, every matrix product of the model. Controls: `quant` holds every
    matrix in a lower precision, `activations` the product's other operand
    too (a product computed IN that precision, as an 8-bit matrix unit does)."""

    def __init__(self, sf, options: dict):
        self.sf, self.quant, self.acts = sf, options.get("quant"), options.get("activations")
        self.kept = {}  # one float32 buffer a matrix shape, written over by the next matrix

    def __call__(self, name: str):
        return _to_f32(self.sf.get_tensor(name))

    def lin(self, x, name: str):
        # the matrix lives until the product is taken, so the next matrix of
        # its shape may write over it: fresh memory for each of 270 matrices
        # (page faults over 19 GB) cost more than the arithmetic
        t = self.sf.get_tensor(name)
        w = _to_f32(t, self.kept.setdefault(t.shape, np.empty(t.shape, np.uint32)))
        if self.quant:
            w = lower_precision(w, self.quant)
        if self.acts:
            x = lower_precision(x, self.acts)
        return x @ w.T


def _to_f32(t: np.ndarray, out: np.ndarray | None = None) -> np.ndarray:
    """bfloat16 -> float32 as a shift of the bits (a bfloat16 is the top half
    of its float32): eighteen times as fast as `astype`, which was a third of
    a run over 9.5 GB of weights."""
    if t.dtype == np.float32:
        return t
    if t.dtype.name != "bfloat16":
        return t.astype(np.float32)
    out = np.empty(t.shape, np.uint32) if out is None else out
    np.left_shift(t.view(np.uint16), 16, out=out, dtype=np.uint32, casting="unsafe")
    return out.view(np.float32)


def forward_logits(ckpt: Path, sequences: list, rows: list, options: dict | None = None) -> list:
    """`sequences`: token id lists; `rows`: one (first position, end) a
    sequence. Returns those rows' logits [end - first, V] a sequence: position
    j's row is the distribution of token j + 1."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy for safetensors)
    from safetensors import safe_open

    options = options or {}
    cfg = json.loads((ckpt / "config.json").read_text())
    eps = cfg.get("layer_norm_eps") or 1e-5
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"], "layer_types and depth disagree"
    with safe_open(str(ckpt / "model.safetensors"), framework="np") as sf:
        get = Weights(sf, options)
        embed = get("model.embed_tokens.weight")
        hs = [embed[np.asarray(t)] for t in sequences]
        if get.quant:  # the head's matrix; the lookup reads the rows as they are
            embed = lower_precision(embed, get.quant)
        bounds = np.cumsum([0] + [len(h) for h in hs])
        for l, kind in enumerate(kinds):
            pre = f"model.layers.{l}."
            w = get(pre + "input_layernorm.weight")
            ns = [layer_norm(h, w, eps) for h in hs]
            # every sequence's rows through the experts together: an expert's
            # matrices are read once
            moe = ffn(np.concatenate(ns), get.lin, pre + "mlp.", cfg, options)
            hs = [h + attention(n, get.lin, pre + "self_attn.", cfg, kind, options)
                  + moe[bounds[i]:bounds[i + 1]] for i, (h, n) in enumerate(zip(hs, ns))]
        w = get("model.norm.weight")
        scale = np.float32(cfg.get("logit_scale", 1.0))
        return [layer_norm(h[lo:hi], w, eps) @ embed.T * scale for h, (lo, hi) in zip(hs, rows)]


def log_softmax(x):
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def teacher_forced_logprobs(ckpt: Path, probes: list, options: dict | None = None) -> list:
    # position j predicts token j + 1
    spans = [(p["prompt_len"] - 1, len(p["tokens"]) - 1) for p in probes]
    out = []
    for p, logits in zip(probes, forward_logits(ckpt, [p["tokens"] for p in probes], spans, options)):
        chosen = np.asarray(p["tokens"][p["prompt_len"]:])
        out.append([float(x) for x in log_softmax(logits)[np.arange(len(chosen)), chosen]])
    return out


def main(argv: list) -> int:
    ckpt, probes_path, out_path = Path(argv[0]), Path(argv[1]), Path(argv[2])
    probes = json.loads(probes_path.read_text())
    out_path.write_text(json.dumps(teacher_forced_logprobs(ckpt, probes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
