"""What `nemotron3-super-ep4`'s logprob tolerance can tell from the served
precision, at FULL WIDTH on the CPU, by the plain reference alone: the same
4 probes x 8 greedy tokens `run.py` compares, computed once as the reference
computes them and once per control in a lower precision. A control stands for
a served run in that precision, so it has to come out as not correct: its
worst |logprob - reference| over the tolerance.

By hand and by name (`NEMOTRON_CONTROLS=1 pytest benchmark/tests/test_controls_nemotron_h.py`):
it writes the 9.3 GB checkpoint into the test's temporary directory and makes
14 passes of about 80 s on 8 cores. `NEMOTRON_CONTROLS_SEED` picks the weights.

The block loop below is `reference/nemotron_h.py`'s `forward_logits` with three
hooks (a cast on every matrix read, a rounding of what the program keeps in
bfloat16, a forced choice of experts); its expert mixer is the reference's with
the choice handed in and out. The first test holds the copy to the reference.
"""

import json
import os
from pathlib import Path

import numpy as np
import pytest

import checkpoint
import run
from checkpoints import nemotron_h as plan
from generators import _draw
from reference import nemotron_h as R

pytestmark = pytest.mark.skipif(
    os.environ.get("NEMOTRON_CONTROLS") != "1",
    reason="full width: 9.3 GB of weights and some twenty minutes; ask by NEMOTRON_CONTROLS=1")

CONF = json.loads((run.HERE / "configs/nemotron3-super-ep4.json").read_text())
ATOL = float(CONF["benchmark"]["logprob_atol"])
SEED = int(os.environ.get("NEMOTRON_CONTROLS_SEED", 2147498831))


def _per_channel(w, top, to):
    """Round a matrix [out, in] to `to`, one scale per output channel."""
    scale = np.abs(w).max(axis=-1, keepdims=True) / top + 1e-30
    return to(w / scale).astype(np.float32) * scale


def int8(w):
    return _per_channel(w, 127.0, np.round)


def fp8(w):
    import ml_dtypes

    return _per_channel(w, 448.0, lambda a: a.astype(ml_dtypes.float8_e4m3fn))


def _bf16(x):
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


def expert_mixer(x, w, cfg, forced):
    """`R.expert_mixer` with the choice of experts handed in (`forced`, or
    None) and out."""
    import jax
    import jax.numpy as jnp

    held, off, K = cfg["n_routed_experts"], cfg.get("moe_expert_offset", 0), cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ w["gate.weight"].T)
    _, idx = jax.lax.top_k(s + w["gate.e_score_correction_bias"], K)
    idx = idx if forced is None else forced
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    wk = cfg.get("routed_scaling_factor", 1.0) * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    dense_w = jnp.sum(wk[..., None] * (idx[..., None] == (off + jnp.arange(held))), axis=-2)
    u = x @ w["fc1_latent_proj.weight"].T

    def one_expert(r, ew):
        w1, w2, weight = ew
        return r + weight[..., None] * (R._relu2(u @ w1.T) @ w2.T), None

    r, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), (w["up"], w["down"], jnp.moveaxis(dense_w, -1, 0)))
    shared = R._relu2(x @ w["shared_experts.up_proj.weight"].T) @ w["shared_experts.down_proj.weight"].T
    return r @ w["fc2_latent_proj.weight"].T + shared, idx


def forward(ckpt: Path, tokens, cast=None, bf16_activations=False, forced=None):
    """(logits [B, L, V], each expert block's choice [B, L, K]). `cast` rounds
    every matrix but the router's (a served 8-bit run keeps the router as it
    is) and the convolution's taps; `bf16_activations` rounds the residual, a
    mixer's input and its output, as the program's bfloat16 does."""
    import jax
    import jax.numpy as jnp
    from safetensors import safe_open

    cfg = json.loads((ckpt / "config.json").read_text())
    cfg.setdefault("layer_norm_epsilon", cfg.get("norm_eps", 1e-5))
    rnd = _bf16 if bf16_activations else (lambda a: a)
    choices = []
    with jax.default_matmul_precision("highest"), \
            safe_open(str(ckpt / "model.safetensors"), framework="np") as sf:
        names = sorted(sf.keys())

        def get(name):
            a = sf.get_tensor(name).astype(np.float32)
            if cast is not None and a.ndim == 2 and "gate." not in name:
                a = cast(a)
            return jnp.asarray(a)

        mixers = {"M": jax.jit(lambda x, w: R.mamba_mixer(x, w, cfg)),
                  "*": jax.jit(lambda x, w: R.attention_mixer(x, w, cfg)),
                  "E": jax.jit(lambda x, w, f: expert_mixer(x, w, cfg, f))}
        h = rnd(get("backbone.embeddings.weight")[np.asarray(tokens)])
        for l, kind in enumerate(cfg["hybrid_override_pattern"]):
            pre = f"backbone.layers.{l}."
            w = {n[len(pre) + len("mixer."):]: get(n) for n in names
                 if n.startswith(pre + "mixer.") and ".experts." not in n}
            x = rnd(R._rms_norm(h, get(pre + "norm.weight"), cfg["layer_norm_epsilon"]))
            if kind == "E":
                for part in ("up", "down"):
                    w[part] = jnp.stack([get(f"{pre}mixer.experts.{e}.{part}_proj.weight")
                                         for e in range(cfg["n_routed_experts"])])
                out, idx = mixers["E"](x, w, None if forced is None else forced[len(choices)])
                choices.append(idx)
            else:
                out = mixers[kind](x, w)
            h = rnd(h + rnd(out))
        h = rnd(R._rms_norm(h, get("backbone.norm_f.weight"), cfg["layer_norm_epsilon"]))
        return h @ get("lm_head.weight").T, choices


def _padded(probes):
    tokens = np.zeros((len(probes), max(len(p["tokens"]) for p in probes)), np.int32)
    for i, p in enumerate(probes):
        tokens[i, : len(p["tokens"])] = p["tokens"]
    return tokens


def _logprobs(logits, probes):
    import jax

    out = []
    for i, p in enumerate(probes):
        n0, n1 = p["prompt_len"], len(p["tokens"])
        logp = np.asarray(jax.nn.log_softmax(logits[i, n0 - 1: n1 - 1], axis=-1))
        out.append(logp[np.arange(n1 - n0), p["tokens"][n0:n1]])
    return np.stack(out)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The checkpoint as `run.py` writes it for SEED, `run.py`'s own probe
    prompts with 8 tokens each chosen greedily by the reference, their
    logprobs, and the experts each row chose."""
    hf = {k: v for k, v in CONF.items() if k not in run.OWN_KEYS}
    ckpt = checkpoint.ensure_checkpoint(tmp_path_factory.mktemp("ckpt"), CONF["name"], hf, SEED, plan)[0]
    probes = [{"tokens": _draw.token_ids(SEED, 800_000 + i, n, hf["vocab_size"]), "prompt_len": n}
              for i, n in enumerate(run.PROBE_LENGTHS)]
    for _ in range(run.PROBE_TOKENS):
        logits, _ = forward(ckpt, _padded(probes))
        for i, p in enumerate(probes):
            p["tokens"].append(int(np.argmax(logits[i, len(p["tokens"]) - 1])))
    logits, choices = forward(ckpt, _padded(probes))
    return {"ckpt": ckpt, "probes": probes, "ref": _logprobs(logits, probes), "choices": choices}


def _worst(bench, **how):
    logits, _ = forward(bench["ckpt"], _padded(bench["probes"]), **how)
    worst = float(np.abs(_logprobs(logits, bench["probes"]) - bench["ref"]).max())
    label = ", ".join(v.__name__ if k == "cast" else k for k, v in how.items())
    print(f"seed {SEED} {label}: worst |logprob - reference| {worst:.4f} (tolerance {ATOL})")
    return worst


def test_the_loop_above_is_the_reference(bench):
    ref = np.asarray(R.teacher_forced_logprobs(bench["ckpt"], bench["probes"]))
    assert np.abs(ref - bench["ref"]).max() < 1e-4


def test_the_healthy_spread_is_the_routers(bench):
    """The served precision (bfloat16 residual and mixer inputs and outputs,
    float32 arithmetic inside) reads as the chip's healthy runs do, inside the
    tolerance; with every row's experts held to the reference's choice it
    reads a quarter of that. Top-22 of 512 sigmoid scores from 0.02-normal
    router weights has a near-tie in a fifth of the rows of every expert
    block, bfloat16 flips it, and a flipped expert is 1/22 of the routed sum:
    the floor under this tolerance is the flips, not the arithmetic."""
    free = _worst(bench, bf16_activations=True)
    held = _worst(bench, bf16_activations=True, forced=bench["choices"])
    assert free < ATOL
    assert held < free / 2 and held < ATOL / 8


@pytest.mark.parametrize("control", [
    "fp8",
    pytest.param("int8", marks=pytest.mark.xfail(strict=True, reason=(
        "ISSUE 29 asked that an 8-bit run fail the tolerance. Integers with a scale per output "
        "channel read 0.15-0.20, inside the healthy runs' own 0.03-0.22 (the router's flips, test "
        "above), so no tolerance the healthy runs pass can fail them. A router with margins needs a "
        "third kind in benchmark/checkpoint.py: PERF.md section 7"))),
])
def test_eight_bit_weights_come_out_as_not_correct(bench, control):
    """The nearest precision below the checkpoint's bfloat16, every matrix
    rounded with one scale per output channel: the 8-bit float (e4m3) has to
    fail the tolerance, with room."""
    assert _worst(bench, cast={"fp8": fp8, "int8": int8}[control]) > 1.2 * ATOL
