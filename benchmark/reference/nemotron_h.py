"""The plain reference for NemotronH (`NemotronHForCausalLM`): one forward pass
in plain `jax.numpy`, float32, `default_matmul_precision("highest")`, a
SEQUENTIAL scan over tokens for the state-space layers (no chunks), no cache,
no kernel, no batching tricks, nothing imported from `dynamo_tpu`.

The model, as the configuration's keys are read. `hybrid_override_pattern` has
one letter a block; every block is `h = h + mixer(RMSNorm(h))` (eps
`layer_norm_epsilon`, weight times normalised x) with ONE mixer; then `norm_f`
and an untied `lm_head`.

- `M`, Mamba-2 (`mamba_num_heads` H x `mamba_head_dim` P = inner, state
  `ssm_state_size` N, `n_groups` G, `conv_kernel` K, conv bias, no projection
  bias): `[z | xBC | dt] = W_in x` with widths inner | inner + 2 G N | H;
  `xBC = silu(causal depthwise conv_K(xBC) + b_conv)`, split into x [H, P],
  B and C [G, N] (head h reads group h // (H / G)); `dt = softplus(dt +
  dt_bias)`; `A = -exp(A_log)`; `S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) (x) B_t`
  with S [H, P, N] from zeros; `y_t = S_t C_t + D x_t`;
  `y = GroupRMSNorm_G(y * silu(z)) * w_norm` (the gate BEFORE the norm, one
  norm per group of inner / G); `out = W_out y`.
- `*`, attention: `num_attention_heads` query and `num_key_value_heads`
  key-value heads of `head_dim`, no bias, causal softmax at 1/sqrt(head_dim),
  NO rotary embedding.
- `E`, latent experts: `s = sigmoid(W_g x)` over the `moe_routed_over` experts
  in float32 on the full hidden state; chosen = top `num_experts_per_tok` of
  `s + e_score_correction_bias`; `w_k = routed_scaling_factor * s_k / (sum of s
  over the chosen + 1e-20)`; `u = W_lat_down x`; `r = sum over the chosen k of
  w_k W2_k relu(W1_k u)^2`; `out = W_lat_up r + W2_s relu(W1_s x)^2`, the shared
  expert on the full hidden state.

Departures from the published model, and assumptions:

- THE SHARE. The checkpoint holds `n_routed_experts` experts of the
  `moe_routed_over` the router scores, ids `moe_expert_offset` onwards, and
  `vocab_size` rows of the published vocabulary: one chip's share of an
  expert-parallel deployment. The sum `r` runs over the chosen experts that are
  HELD; what the others would add is left out, here as in the program, and the
  weights `w_k` are still normalised over everything chosen. Where the keys
  `moe_routed_over` / `moe_expert_offset` are absent, all experts are held.
- Depth is what `num_hidden_layers` and the pattern say; the multi-token
  prediction module (`mtp_hybrid_override_pattern`) is a draft head outside the
  blocks' logits and is not read.
- No rotary embedding in the attention blocks: the published `nemotron_h`
  modeling code applies none, and `rope_theta` / `partial_rotary_factor` are
  inert keys (an assumption: no network here to read the code again).
- The router reads the full hidden state (not the latent one).
- `n_group` = `topk_group` = 1 (no group limit), `norm_topk_prob` true, one
  shared expert, `mlp_hidden_act` `relu2`: other values are not implemented.
- `dt` is not clamped (`time_step_limit` (0, inf)); `time_step_min/max/floor`
  only shape the published initialisation of `dt_bias`.
- The state is float32 from zeros; `residual_in_fp32` false is the program's
  bfloat16 residual, which this float32 pass does not imitate.
- Tensor names are the HF layout as the builder of PR 29 knew it
  (`backbone.layers.N.mixer.*`, `gate.e_score_correction_bias`,
  `fc1_latent_proj` / `fc2_latent_proj`, `shared_experts`), unchecked without a
  network; `benchmark/checkpoints/nemotron_h.py` writes the same names.

The checkpoint is read one block at a time and cast to float32, an expert
block one expert at a time: an expert's two products run in numpy float32 over
the rows that chose it (a number of rows of its own for every expert, which
`jax.numpy` would compile one by one), everything else in `jax.numpy`. Every
held expert over every row, with a zero weight where it was not chosen, is
8.7 TFLOP of float32 at this configuration's size and 42 s of a run that has
360 (PR 29); the rows that chose it are a twenty-third of that. Run as a script
by `benchmark/run.py`, in a process of its own held to the CPU:

    JAX_PLATFORMS=cpu python benchmark/reference/nemotron_h.py CKPT PROBES.json OUT.json

PROBES.json: [{"tokens": [prompt ids ..., chosen ids ...], "prompt_len": n}].
OUT.json: [[log p(tokens[i] | tokens[:i]) for i in prompt_len..len-1], ...].
"""

from __future__ import annotations

import json
import sys
from pathlib import Path


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _relu2(x):
    import jax.numpy as jnp

    return jnp.maximum(x, 0.0) ** 2


def mamba_mixer(x, w, cfg):
    """x [B, L, D] (already normed) -> [B, L, D]."""
    import jax
    import jax.numpy as jnp

    B, L, _ = x.shape
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    G, K = cfg["n_groups"], cfg["conv_kernel"]
    inner, gn = H * P, G * N
    proj = x @ w["in_proj.weight"].T
    z, xbc, dt = proj[..., :inner], proj[..., inner:inner + inner + 2 * gn], proj[..., 2 * inner + 2 * gn:]
    # causal depthwise convolution: output t reads inputs t-K+1 .. t
    cw = w["conv1d.weight"][:, 0, :]  # [C, K]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + L] * cw[:, k] for k in range(K)) + w["conv1d.bias"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :inner].reshape(B, L, H, P)
    Bm = jnp.repeat(xbc[..., inner:inner + gn].reshape(B, L, G, N), H // G, axis=2)
    Cm = jnp.repeat(xbc[..., inner + gn:].reshape(B, L, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [B, L, H]
    A = -jnp.exp(w["A_log"])

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp  # [B,H,P], [B,H], [B,H,N], [B,H,N]
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t) + w["D"][None, :, None] * x_t

    t_first = lambda a: jnp.swapaxes(a, 0, 1)
    _, y = jax.lax.scan(
        step, jnp.zeros((B, H, P, N), jnp.float32),
        (t_first(xs), t_first(dt), t_first(Bm), t_first(Cm)),
    )
    y = t_first(y).reshape(B, L, inner) * jax.nn.silu(z)
    yg = y.reshape(B, L, G, inner // G)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["layer_norm_epsilon"])
    return (yg.reshape(B, L, inner) * w["norm.weight"]) @ w["out_proj.weight"].T


def attention_mixer(x, w, cfg):
    import jax
    import jax.numpy as jnp

    B, L, D = x.shape
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    q = (x @ w["q_proj.weight"].T).reshape(B, L, Hq, hd)
    k = jnp.repeat((x @ w["k_proj.weight"].T).reshape(B, L, Hkv, hd), Hq // Hkv, axis=2)
    v = jnp.repeat((x @ w["v_proj.weight"].T).reshape(B, L, Hkv, hd), Hq // Hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(B, L, Hq * hd) @ w["o_proj.weight"].T


def expert_mixer(x, w, cfg):
    """The held experts' part of the routed sum, plus the shared expert. Not
    for `jax.jit`: the rows an expert serves are gathered on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    held, off = cfg["n_routed_experts"], cfg.get("moe_expert_offset", 0)
    K = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["gate.weight"].T)  # [B, L, routed over]
    _, idx = jax.lax.top_k(s + w["gate.e_score_correction_bias"], K)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    wk = cfg.get("routed_scaling_factor", 1.0) * chosen \
        / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    u = x @ w["fc1_latent_proj.weight"].T
    # one expert at a time, over the rows that chose it: numpy float32, since
    # each expert has its own number of rows (a shape of its own for a jit)
    idx_r, wk_r = np.asarray(idx).reshape(-1, K), np.asarray(wk).reshape(-1, K)
    u_r = np.asarray(u).reshape(-1, u.shape[-1])
    r = np.zeros_like(u_r)
    for e in range(held):
        hit = idx_r == off + e
        rows = np.flatnonzero(hit.any(axis=-1))
        if rows.size == 0:
            continue  # and its matrices are never read
        w1 = np.asarray(w[f"experts.{e}.up_proj.weight"])
        w2 = np.asarray(w[f"experts.{e}.down_proj.weight"])
        weight = np.sum(wk_r[rows] * hit[rows], axis=-1, keepdims=True)
        r[rows] += weight * (np.maximum(u_r[rows] @ w1.T, 0.0) ** 2 @ w2.T)
    shared = _relu2(x @ w["shared_experts.up_proj.weight"].T) @ w["shared_experts.down_proj.weight"].T
    return jnp.asarray(r.reshape(u.shape)) @ w["fc2_latent_proj.weight"].T + shared


MIXERS = {"M": mamba_mixer, "*": attention_mixer, "E": expert_mixer}


class _Weights:
    """The checkpoint's tensors by name, float32. Each is cast, when it is
    asked for, into the buffer kept for its ROLE (its name with the numbers
    taken out: `backbone.layers.#.mixer.experts.#.up_proj.weight`), so the next
    tensor of that role writes over it, and a caller is done with one block's
    tensors before it asks for the next block's: new memory for each of 1400
    tensors costs more than the arithmetic."""

    def __init__(self, sf):
        self.sf, self.kept = sf, {}

    def __call__(self, name: str):
        import re

        import numpy as np

        t = self.sf.get_tensor(name)
        role = re.sub(r"\d+", "#", name)
        if role not in self.kept or self.kept[role].shape != t.shape:
            self.kept[role] = np.empty(t.shape, np.float32)
        self.kept[role][...] = t
        return self.kept[role]


class _Block:
    """One block's tensors by their suffix, each read when it is asked for: an
    expert block holds gigabytes, and asks for one expert at a time."""

    def __init__(self, get, prefix: str):
        self.get, self.prefix = get, prefix

    def __getitem__(self, suffix: str):
        return self.get(self.prefix + suffix)


def forward_logits(ckpt: Path, tokens, rows=None):
    """Logits [B, L, V] of the whole model over `tokens` [B, L] (int array):
    position j's row is the distribution of token j + 1. With `rows`
    [(sequence, first position, end)], a list of those rows' logits only."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy for safetensors)
    import jax
    import numpy as np
    from safetensors import safe_open

    cfg = json.loads((ckpt / "config.json").read_text())
    eps = cfg.setdefault("layer_norm_epsilon", cfg.get("norm_eps", 1e-5))
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"], "pattern and depth disagree"
    with jax.default_matmul_precision("highest"), \
            safe_open(str(ckpt / "model.safetensors"), framework="np") as sf:
        names = sorted(sf.keys())
        get = _Weights(sf)
        h = sf.get_tensor("backbone.embeddings.weight")[np.asarray(tokens)].astype(np.float32)
        steps = {kind: jax.jit(lambda x, w, f=f: f(x, w, cfg))
                 for kind, f in MIXERS.items() if kind != "E"}
        for l, kind in enumerate(pattern):
            pre = f"backbone.layers.{l}."
            x = _rms_norm(h, get(pre + "norm.weight"), eps)
            if kind == "E":
                out = expert_mixer(x, _Block(get, pre + "mixer."), cfg)
            else:
                out = steps[kind](x, {n[len(pre) + len("mixer."):]: get(n) for n in names
                                      if n.startswith(pre + "mixer.")})
            # on the host, so that every product has ended before the next
            # block's tensors write over this block's
            h = np.asarray(h + out)
        h = _rms_norm(h, get("backbone.norm_f.weight"), eps)
        head = get("lm_head.weight")
        if rows is None:
            return np.asarray(h @ head.T)
        return [np.asarray(h[i, lo:hi] @ head.T) for i, lo, hi in rows]


def teacher_forced_logprobs(ckpt: Path, probes: list) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    L = max(len(p["tokens"]) for p in probes)
    tokens = np.zeros((len(probes), L), np.int32)  # right-padded: causal, so harmless
    for i, p in enumerate(probes):
        tokens[i, : len(p["tokens"])] = p["tokens"]
    # position j predicts token j + 1
    spans = [(i, p["prompt_len"] - 1, len(p["tokens"]) - 1) for i, p in enumerate(probes)]
    out = []
    for p, logits in zip(probes, forward_logits(ckpt, tokens, spans)):
        logp = jax.nn.log_softmax(logits, axis=-1)
        chosen = jnp.asarray(p["tokens"][p["prompt_len"]:])
        out.append([float(x) for x in logp[jnp.arange(len(chosen)), chosen]])
    return out


def main(argv: list) -> int:
    ckpt, probes_path, out_path = Path(argv[0]), Path(argv[1]), Path(argv[2])
    probes = json.loads(probes_path.read_text())
    out_path.write_text(json.dumps(teacher_forced_logprobs(ckpt, probes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
