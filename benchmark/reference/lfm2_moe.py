"""The plain reference for LFM2-MoE (`Lfm2MoeForCausalLM`, LiquidAI
LFM2-8B-A1B): one forward pass in numpy float32 (every product at float32's
full precision), no cache, no kernel, no batching, nothing imported from
`dynamo_tpu`.

The model, as the configuration's keys are read (ISSUE 42 wrote the reading
out from the published `modeling_lfm2_moe` as its writer knew it):

- Embedding over `vocab_size` ids; `num_hidden_layers` blocks; `embedding_norm`
  (RMSNorm); the head is the embedding (tied).
- A block: `h = h + op(RMSNorm_operator(h))`, then `h = h + ffn(RMSNorm_ffn(h))`.
  RMSNorm divides by `sqrt(mean(x^2) + norm_eps)` and multiplies by a weight.
- `layer_types[l]` = `conv`, a gated short convolution: `B, C, x =
  split3(W_in u)` in that order; `z_t = sum_k w_k (B*x)_{t-K+1+k}` per channel
  with K = `conv_L_cache` taps (depthwise, causal, zeros before the sequence,
  no bias, no activation); `y = W_out (C * z)`.
- `full_attention`: `num_attention_heads` query and `num_key_value_heads`
  key-value heads of hidden / heads, no bias; RMSNorm over the head's lanes on
  q and on k (one weight each, shared by the heads), THEN rope by halves over
  the whole head at `rope_theta`; causal softmax at 1/sqrt(head_dim); `W_o`.
- FFN of the first `num_dense_layers` blocks: `W_2 (silu(W_1 u) * W_3 u)`.
  Of the others: `s = sigmoid(W_g u)` over `num_experts`; chosen = top
  `num_experts_per_tok` of `s + expert_bias` (the bias moves the choice only);
  `w = routed_scaling_factor * s_chosen / (sum of s over the chosen + 1e-6)`
  (`norm_topk_prob`); the sum over the chosen of `w_e W_2e (silu(W_1e u) *
  W_3e u)`. No shared expert.

Assumptions (the configuration lists them too): the tie, the tensor names
(`benchmark/checkpoints/lfm2_moe.py` writes the same), the order B, C, x of
`in_proj`'s thirds, rope by halves, `conv.conv.weight` [C, 1, K] with tap k on
the input K-1-k positions back. Where the keys `moe_routed_over` /
`moe_expert_offset` are present the checkpoint holds a share of the experts
and the routed sum runs over the chosen experts that are held, as in the
program (the published model and the benchmark's configuration hold all).

The checkpoint is read one tensor at a time and cast to float32. An expert's
three products run over the rows that chose it; the head is taken on the
compared rows only; attention runs in blocks of query rows, so that the
16-layer model at published widths fits the CPU child. `options` are the
controls of `benchmark/tests/test_controls_lfm2_moe.py` (a lost or late
convolution window at the hand-off from prefill to decode, no QK-norm, the
selection bias in the weights, a lower precision of the matrices); none is set
in a benchmark run.

    JAX_PLATFORMS=cpu python benchmark/reference/lfm2_moe.py CKPT PROBES.json OUT.json

PROBES.json: [{"tokens": [prompt ids ..., chosen ids ...], "prompt_len": n}].
OUT.json: [[log p(tokens[i] | tokens[:i]) for i in prompt_len..len-1], ...].
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

#: query rows of one attention block
BLOCK_ROWS = 256
ROUTING_EPS = 1e-6


def rms_norm(x, w, eps):
    return x / np.sqrt((x * x).mean(axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x / (1.0 + np.exp(-x))


def rope(x, positions, theta: float):
    """x [L, H, hd], by halves: lane i turns with lane i + hd/2."""
    hd = x.shape[-1]
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = positions.astype(np.float32)[:, None] * inv[None]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def taps(bx, w):
    """bx [L, C], w [C, K] -> z [L, C]: z_t = sum_k w[:, k] bx_{t-K+1+k}."""
    L, K = bx.shape[0], w.shape[1]
    padded = np.concatenate([np.zeros((K - 1, bx.shape[1]), bx.dtype), bx])
    return sum(padded[k:k + L] * w[:, k] for k in range(K))


def short_conv(u, get, prefix: str, handoff: int, options: dict):
    """u [L, D] (normed) of ONE sequence from position 0 -> [L, D]. `handoff`
    is the first position a served run decodes: the controls `conv_window`
    `lost` (the decode steps start from a window of zeros) and `late` (from
    the window of a position earlier) change what rows from there on see."""
    B, C, x = np.split(get.lin(u, prefix + "in_proj.weight"), 3, axis=-1)
    bx = B * x
    w = get(prefix + "conv.weight")[:, 0, :]
    z = taps(bx, w)
    fault = options.get("conv_window")
    if fault == "lost":
        z[handoff:] = taps(np.concatenate([np.zeros_like(bx[:handoff]), bx[handoff:]]), w)[handoff:]
    elif fault == "late":
        z[handoff:] = taps(np.concatenate([bx[:handoff - 1], bx[handoff:]]), w)[handoff - 1:]
    return get.lin(C * z, prefix + "out_proj.weight")


def attention(u, get, prefix: str, cfg: dict, options: dict):
    """u [L, D] (normed) of ONE sequence from position 0 -> [L, D]."""
    L, D = u.shape
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    G = Hq // Hkv
    eps = cfg.get("norm_eps") or 1e-5
    q = get.lin(u, prefix + "q_proj.weight").reshape(L, Hq, hd)
    k = get.lin(u, prefix + "k_proj.weight").reshape(L, Hkv, hd)
    v = get.lin(u, prefix + "v_proj.weight").reshape(L, Hkv, hd)
    if options.get("qk_norm", True):
        q = rms_norm(q, get(prefix + "q_layernorm.weight"), eps)
        k = rms_norm(k, get(prefix + "k_layernorm.weight"), eps)
    pos = np.arange(L)
    theta = float(cfg.get("rope_theta") or (cfg.get("rope_parameters") or {}).get("rope_theta") or 1e6)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    out = np.empty((L, Hq, hd), np.float32)
    scale = np.float32(1.0 / np.sqrt(hd))
    for lo in range(0, L, BLOCK_ROWS):
        hi = min(L, lo + BLOCK_ROWS)
        seen = np.arange(hi)[None, :] <= np.arange(lo, hi)[:, None]
        for h in range(Hkv):
            qs = q[lo:hi, h * G:(h + 1) * G].transpose(1, 0, 2)  # [G, rows, hd]
            scores = qs @ k[:hi, h].T * scale  # [G, rows, keys]
            scores = np.where(seen[None], scores, -np.inf)
            scores -= scores.max(axis=-1, keepdims=True)
            p = np.exp(scores)
            p /= p.sum(axis=-1, keepdims=True)
            out[lo:hi, h * G:(h + 1) * G] = (p @ v[:hi, h]).transpose(1, 0, 2)
    return get.lin(out.reshape(L, Hq * hd), prefix + "out_proj.weight")


def swiglu(rows, get, prefix: str):
    mid = silu(get.lin(rows, prefix + "w1.weight")) * get.lin(rows, prefix + "w3.weight")
    return get.lin(mid, prefix + "w2.weight")


def experts(u, get, prefix: str, cfg: dict, options: dict):
    """u [R, D] -> the routed sum [R, D] (the held experts' part)."""
    held = cfg["num_experts"]
    off = cfg.get("moe_expert_offset", 0)
    K = cfg["num_experts_per_tok"]
    s = 1.0 / (1.0 + np.exp(-get.lin(u, prefix + "gate.weight")))  # [R, routed over]
    biased = s + get(prefix + "expert_bias")
    idx = np.argsort(-biased, axis=-1, kind="stable")[:, :K]
    chosen = np.take_along_axis(biased if options.get("bias_in_weights") else s, idx, axis=-1)
    wk = cfg.get("routed_scaling_factor", 1.0) * chosen / (chosen.sum(axis=-1, keepdims=True) + ROUTING_EPS)
    out = np.zeros_like(u)
    for e in range(held):
        hit = idx == off + e
        rows = np.flatnonzero(hit.any(axis=-1))
        if rows.size == 0:
            continue  # and its matrices are never read
        weight = (wk[rows] * hit[rows]).sum(axis=-1, keepdims=True)
        out[rows] += weight * swiglu(u[rows], get, f"{prefix}experts.{e}.")
    return out


def lower_precision(t: np.ndarray, kind: str) -> np.ndarray:
    """A [rows, in] array as it would be held in `kind`, one scale per row (a
    matrix's output channel): `fp8` float8 e4m3 (the nearest floating
    precision below bfloat16), `int8` symmetric. Back in float32."""
    import ml_dtypes

    amax = np.maximum(np.abs(t).max(axis=-1, keepdims=True), 1e-30)
    if kind == "fp8":
        scale = amax / 448.0
        return (t / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale
    scale = amax / 127.0
    return np.round(t / scale).clip(-127, 127) * scale


class Weights:
    """The checkpoint's tensors by name, float32, read when asked for; and
    `lin`, every matrix product of the model. The control `quant` holds every
    matrix in a lower precision."""

    def __init__(self, sf, options: dict):
        self.sf, self.quant = sf, options.get("quant")
        self.kept = {}  # one float32 buffer a matrix shape, written over by the next matrix

    def __call__(self, name: str):
        return _to_f32(self.sf.get_tensor(name))

    def lin(self, x, name: str):
        # the matrix lives until the product is taken, so the next matrix of
        # its shape may write over it (fresh memory for each of 1400 matrices
        # costs more than the arithmetic)
        t = self.sf.get_tensor(name)
        w = _to_f32(t, self.kept.setdefault(t.shape, np.empty(t.shape, np.uint32)))
        if self.quant:
            w = lower_precision(w, self.quant)
        return x @ w.T


def _to_f32(t: np.ndarray, out: np.ndarray | None = None) -> np.ndarray:
    """bfloat16 -> float32 as a shift of the bits (a bfloat16 is the top half
    of its float32)."""
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
    j's row is the distribution of token j + 1. The first position is the last
    of the prompt, so the next is where a served run starts to decode."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy for safetensors)
    from safetensors import safe_open

    options = options or {}
    cfg = json.loads((ckpt / "config.json").read_text())
    eps = cfg.get("norm_eps") or 1e-5
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
            w = get(pre + "operator_norm.weight")
            if kind == "conv":
                hs = [h + short_conv(rms_norm(h, w, eps), get, pre + "conv.", lo + 1, options)
                      for h, (lo, _) in zip(hs, rows)]
            else:
                hs = [h + attention(rms_norm(h, w, eps), get, pre + "self_attn.", cfg, options)
                      for h in hs]
            # every sequence's rows through the FFN together: a matrix is read once
            u = rms_norm(np.concatenate(hs), get(pre + "ffn_norm.weight"), eps)
            if l < cfg["num_dense_layers"]:
                ffn = swiglu(u, get, pre + "feed_forward.")
            else:
                ffn = experts(u, get, pre + "feed_forward.", cfg, options)
            hs = [h + ffn[bounds[i]:bounds[i + 1]] for i, h in enumerate(hs)]
        w = get("model.embedding_norm.weight")
        return [rms_norm(h[lo:hi], w, eps) @ embed.T for h, (lo, hi) in zip(hs, rows)]


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
