"""The plain reference for Falcon-H1 (`FalconH1ForCausalLM`, tiiuae): one
forward pass in plain `jax.numpy`, float32,
`default_matmul_precision("highest")`, a SEQUENTIAL scan over tokens for the
state-space mixer (no chunks), full attention with no cache, no kernel, one
sequence at a time, nothing imported from `dynamo_tpu`.

The model, as the configuration's keys are read (ISSUE 45 wrote the equations
out from the catalog row's `config` and `described_as`: "parallel Mamba-2 +
attention heads per block"). Every block runs BOTH mixers on the same normed
input and adds them; RMSNorm divides by `sqrt(mean(x^2) + rms_norm_eps)` and
multiplies by a weight.

    h0   = E[ids] * embedding_multiplier
    u    = RMSNorm(h; input_layernorm)
    # Mamba-2 (mamba_d_ssm = mamba_n_heads H x mamba_d_head P; mamba_expand inert)
    p    = (W_in (u * ssm_in_multiplier)) * m       # z inner | x inner | B G*N | C G*N | dt H
           m = ssm_multipliers over those five segments, in that order
    xBC  = silu(causal_conv1d(x|B|C; w [C, 1, mamba_d_conv]) + bias)
    dt   = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + (dt_t x_t) (x) B_t ;  y_t = S_t C_t + D x_t
           S [H, P, N] float32 from zeros; head j reads group j // (H / G)
    y    = GroupRMSNorm_G(y * silu(z); mamba.norm)  # the gate BEFORE the norm
    m_out = (W_out y) * ssm_out_multiplier
    # attention, on the same u
    u'   = u * attention_in_multiplier
    q = W_q u' ; k = (W_k u') * key_multiplier ; v = W_v u'   # no bias, no q/k norm
    q, k = rope(q, k; by halves over the whole head, rope_theta, no scaling)
    a_out = (W_o softmax_causal(q k^T / sqrt(head_dim)) v) * attention_out_multiplier
    h    = h + m_out + a_out
    f    = RMSNorm(h; pre_ff_layernorm)
    h    = h + (W_down (silu((W_gate f) * mlp_multipliers[0]) * (W_up f))) * mlp_multipliers[1]
    logits = (W_head RMSNorm(h_L; final_layernorm)) * lm_head_multiplier      # untied

Assumptions (the configuration lists them too; no network here to read the
published `modeling_falcon_h1` again): the order of the five segments of `m`
and of `in_proj`'s output; the tensor names (`benchmark/checkpoints/
falcon_h1.py` writes the same); dt not clamped; the state in float32 from
zeros; `conv1d.weight` [C, 1, K] with tap k on the input K-1-k positions back.
Depth is what `num_hidden_layers` says (a cut in depth is the first layers).

The checkpoint is read one LAYER at a time and cast to float32 (1.7 GB a
layer; 21 GB of float32 for the whole cut would not be needed at once), the
head in slices of the vocabulary, over the compared rows only. `options` are
the controls of `benchmark/tests/test_controls_falcon_h1.py`; none is set in a
benchmark run:

    rope False              no rotary embedding
    decode_position_skew n  positions from the hand-off on are n too high
    without [names]         multipliers left out (set to 1): `key_multiplier`,
                            `ssm_multipliers.3` (C's segment), ...
    attention False         the attention branch dropped
    mamba False             the Mamba branch dropped
    state "lost"            the decode steps start from a state of zeros
    conv_window "late"      ... from the convolution window of a position earlier
    quant "fp8" | "int8"    every matrix in a lower precision, a scale per
                            output channel

The hand-off is the first position a served run decodes (its prompt's length).

    JAX_PLATFORMS=cpu python benchmark/reference/falcon_h1.py CKPT PROBES.json OUT.json

PROBES.json: [{"tokens": [prompt ids ..., chosen ids ...], "prompt_len": n}].
OUT.json: [[log p(tokens[i] | tokens[:i]) for i in prompt_len..len-1], ...].
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

#: rows of the vocabulary one product of the head takes
HEAD_ROWS = 32768
SEGMENTS = ("z", "x", "B", "C", "dt")


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def multipliers(cfg: dict, options: dict) -> dict:
    """The configuration's forward multipliers by name, the lists by
    `name.index`; those `options["without"]` names are 1."""
    out = {}
    for key, value in cfg.items():
        if key.endswith("_multiplier"):
            out[key] = float(value)
        elif key.endswith("_multipliers"):
            out.update({f"{key}.{i}": float(v) for i, v in enumerate(value)})
    for name in options.get("without", ()):
        if name not in out:
            raise KeyError(f"no multiplier {name!r} in {sorted(out)}")
        out[name] = 1.0
    return out


def mamba_mixer(u, w, cfg, mult, handoff, options):
    """u [L, D] (normed) of ONE sequence from position 0 -> [L, D]."""
    import jax
    import jax.numpy as jnp

    L = u.shape[0]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    G, K = cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    inner, gn = H * P, G * N
    p = (u * mult["ssm_in_multiplier"]) @ w["mamba.in_proj.weight"].T
    widths = dict(zip(SEGMENTS, (inner, inner, gn, gn, H)))
    parts, at = {}, 0
    for i, name in enumerate(SEGMENTS):
        parts[name] = p[:, at:at + widths[name]] * mult[f"ssm_multipliers.{i}"]
        at += widths[name]
    xbc = jnp.concatenate([parts["x"], parts["B"], parts["C"]], axis=-1)
    # causal depthwise convolution: output t reads inputs t-K+1 .. t
    cw = w["mamba.conv1d.weight"][:, 0, :]  # [C, K]

    def taps(inputs):
        padded = jnp.pad(inputs, ((K - 1, 0), (0, 0)))
        return sum(padded[k:k + L] * cw[:, k] for k in range(K))

    conv = taps(xbc)
    if options.get("conv_window") == "late":
        # rows from the hand-off on see the inputs before it one position late
        late = taps(jnp.concatenate([xbc[:1] * 0, xbc[:handoff - 1], xbc[handoff:]]))
        conv = jnp.where((jnp.arange(L) >= handoff)[:, None], late, conv)
    xbc = jax.nn.silu(conv + w["mamba.conv1d.bias"])
    xs = xbc[:, :inner].reshape(L, H, P)
    Bm = jnp.repeat(xbc[:, inner:inner + gn].reshape(L, G, N), H // G, axis=1)
    Cm = jnp.repeat(xbc[:, inner + gn:].reshape(L, G, N), H // G, axis=1)
    dt = jax.nn.softplus(parts["dt"] + w["mamba.dt_bias"])  # [L, H]
    A = -jnp.exp(w["mamba.A_log"])
    lost = options.get("state") == "lost"

    def step(S, inp):
        x_t, dt_t, B_t, C_t, t = inp  # [H,P], [H], [H,N], [H,N], scalar
        if lost:
            S = jnp.where(t == handoff, 0.0, S)
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + w["mamba.D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, dt, Bm, Cm, jnp.arange(L)))
    y = y.reshape(L, inner) * jax.nn.silu(parts["z"])
    yg = y.reshape(L, G, inner // G)
    yg = yg / jnp.sqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    y = yg.reshape(L, inner) * w["mamba.norm.weight"]
    return (y @ w["mamba.out_proj.weight"].T) * mult["ssm_out_multiplier"]


def _rope(x, positions, theta: float):
    """x [L, H, hd], by halves: lane i turns with lane i + hd/2."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_mixer(u, w, cfg, mult, handoff, options):
    import jax
    import jax.numpy as jnp

    L, D = u.shape
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    u = u * mult["attention_in_multiplier"]
    q = (u @ w["self_attn.q_proj.weight"].T).reshape(L, Hq, hd)
    k = ((u @ w["self_attn.k_proj.weight"].T) * mult["key_multiplier"]).reshape(L, Hkv, hd)
    v = (u @ w["self_attn.v_proj.weight"].T).reshape(L, Hkv, hd)
    if options.get("rope", True):
        pos = jnp.arange(L)
        pos = pos + jnp.where(pos >= handoff, int(options.get("decode_position_skew", 0)), 0)
        q, k = _rope(q, pos, float(cfg["rope_theta"])), _rope(k, pos, float(cfg["rope_theta"]))
    k, v = jnp.repeat(k, Hq // Hkv, axis=1), jnp.repeat(v, Hq // Hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return (attn.reshape(L, Hq * hd) @ w["self_attn.o_proj.weight"].T) * mult["attention_out_multiplier"]


def layer(h, w, cfg, mult, handoff, options):
    """One block over ONE sequence: h [L, D] -> [L, D]."""
    import jax
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    u = _rms_norm(h, w["input_layernorm.weight"], eps)
    if options.get("mamba", True):
        h = h + mamba_mixer(u, w, cfg, mult, handoff, options)
    if options.get("attention", True):
        h = h + attention_mixer(u, w, cfg, mult, handoff, options)
    f = _rms_norm(h, w["pre_ff_layernorm.weight"], eps)
    gate = jax.nn.silu((f @ w["feed_forward.gate_proj.weight"].T) * mult["mlp_multipliers.0"])
    ffn = (gate * (f @ w["feed_forward.up_proj.weight"].T)) @ w["feed_forward.down_proj.weight"].T
    return h + ffn * mult["mlp_multipliers.1"]


def lower_precision(t, kind: str):
    """A [rows, in] array as it would be held in `kind`, one scale per row (a
    matrix's output channel): `fp8` float8 e4m3 (the nearest floating
    precision below bfloat16), `int8` symmetric. Back in float32."""
    import ml_dtypes
    import numpy as np

    amax = np.maximum(np.abs(t).max(axis=-1, keepdims=True), 1e-30)
    if kind == "fp8":
        scale = amax / 448.0
        return (t / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale
    scale = amax / 127.0
    return (np.round(t / scale).clip(-127, 127) * scale).astype(np.float32)


class _Weights:
    """The checkpoint's tensors by name, float32. Each is cast, when it is
    asked for, into the buffer kept for its ROLE (its name with the layer's
    number taken out), so the next layer's tensor of that role writes over it:
    a caller is done with one layer's tensors before it asks for the next
    layer's. The control `quant` holds every matrix in a lower precision."""

    def __init__(self, sf, options: dict):
        self.sf, self.kept, self.quant = sf, {}, options.get("quant")

    def __call__(self, name: str, rows: slice | None = None):
        import numpy as np

        t = self.sf.get_slice(name)[rows] if rows is not None else self.sf.get_tensor(name)
        role = re.sub(r"\d+", "#", name)
        if role not in self.kept or self.kept[role].shape != t.shape:
            self.kept[role] = np.empty(t.shape, np.float32)
        self.kept[role][...] = t
        if self.quant and t.ndim == 2 and name != "model.embed_tokens.weight":
            self.kept[role][...] = lower_precision(self.kept[role], self.quant)
        return self.kept[role]


def forward_logits(ckpt: Path, sequences: list, rows: list, options: dict | None = None) -> list:
    """`sequences`: token id lists; `rows`: one (first position, end) a
    sequence. Returns those rows' logits [end - first, V] a sequence: position
    j's row is the distribution of token j + 1. The first position is the last
    of the prompt, so the next is where a served run starts to decode."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy for safetensors)
    import jax
    import numpy as np
    from safetensors import safe_open

    options = options or {}
    cfg = json.loads((ckpt / "config.json").read_text())
    cfg.setdefault("rms_norm_eps", 1e-5)
    mult = multipliers(cfg, options)
    with jax.default_matmul_precision("highest"), \
            safe_open(str(ckpt / "model.safetensors"), framework="np") as sf:
        names = sorted(sf.keys())
        get = _Weights(sf, options)
        embed = sf.get_tensor("model.embed_tokens.weight")
        hs = [embed[np.asarray(seq)].astype(np.float32) * np.float32(mult["embedding_multiplier"])
              for seq in sequences]
        del embed
        step = jax.jit(lambda h, w, handoff: layer(h, w, cfg, mult, handoff, options),
                       static_argnums=2)
        for l in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{l}."
            w = {n[len(pre):]: get(n) for n in names if n.startswith(pre)}
            # on the host, so that every product has ended before the next
            # layer's tensors write over this layer's
            hs = [np.asarray(step(h, w, lo + 1)) for h, (lo, _) in zip(hs, rows)]
        norm = get("model.final_layernorm.weight")
        hs = [np.asarray(_rms_norm(h[lo:hi], norm, cfg["rms_norm_eps"])) for h, (lo, hi) in zip(hs, rows)]
        V = cfg["vocab_size"]
        logits = [np.empty((len(h), V), np.float32) for h in hs]
        for at in range(0, V, HEAD_ROWS):
            head = get("lm_head.weight", slice(at, min(V, at + HEAD_ROWS)))
            for h, out in zip(hs, logits):
                out[:, at:at + len(head)] = np.asarray(h @ head.T)
        return [out * np.float32(mult["lm_head_multiplier"]) for out in logits]


def log_softmax(x):
    import numpy as np

    x = x.astype(np.float64)
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def teacher_forced_logprobs(ckpt: Path, probes: list, options: dict | None = None) -> list:
    import numpy as np

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
