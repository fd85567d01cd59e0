"""What `command-a-plus-ep8`'s logprob tolerance can tell from the served
model, at FULL WIDTH on the CPU, by the plain reference alone: the harness's 4
probes (48-300 tokens) x 8 greedy tokens, computed once as the reference
computes them and once per control. A control stands for a served run of
another model or in a lower precision, so it has to come out as not correct
by the harness's own comparison (`run.py` `run_cell`: `worst <= logprob_atol`):
its worst |logprob - reference| over the tolerance. The 8-bit floats are held
to that too: the limit stands under the smaller of them (the configuration's
`logprob_atol_why` has both readings and what is left of room).

  fp8              every matrix product computed in float8 e4m3, the nearest
                   floating precision below the checkpoint's bf16: the matrix
                   with one scale per output channel, the activations with one
                   per token (what an 8-bit matrix unit is given)
  fp8_weights      the matrices alone in float8 e4m3 (PR 29's control)
  int8             every matrix in int8, one scale per output channel
  rope_by_halves   the sliding layers' rope by halves (NeoX), not by pairs
  shared_summed    the four shared experts summed, not averaged
  expert_zeroed    one held expert's part left out

By hand and by name (`COMMAND_A_CONTROLS=1 pytest benchmark/tests/test_controls_cohere2_moe.py -s`):
it writes the 9.47 GB checkpoint into `benchmark/.cache` (or finds it there)
and makes 14 passes of about 30 s on 8 cores. `COMMAND_A_CONTROLS_SEED` picks
the weights. The window's mask is out of these probes' reach (300 tokens
under a window of 4096): `chip_long_probe.py` holds it at 6k and 12k tokens.
"""

import json
import os

import numpy as np
import pytest

import checkpoint
import run
from checkpoints import cohere2_moe as plan
from generators import _draw
from reference import cohere2_moe as R

pytestmark = pytest.mark.skipif(
    os.environ.get("COMMAND_A_CONTROLS") != "1",
    reason="full width: 9.47 GB of weights and some ten minutes; ask by COMMAND_A_CONTROLS=1")

CONF = json.loads((run.HERE / "configs/command-a-plus-ep8.json").read_text())
ATOL = float(CONF["benchmark"]["logprob_atol"])
SEED = int(os.environ.get("COMMAND_A_CONTROLS_SEED", 2147498837))

CONTROLS = {
    "fp8": {"quant": "fp8", "activations": "fp8"},
    "fp8_weights": {"quant": "fp8"},
    "int8": {"quant": "int8"},
    "rope_by_halves": {"rope": "halves"},
    "shared_summed": {"shared": "sum"},
    "expert_zeroed": {"zero_expert": 3},
}
#: what the tolerance cannot tell from the served model: a flipped router
#: choice moves the healthy runs as far (`logprob_atol_why`)
BELOW_IS_FINE = {"int8", "expert_zeroed"}


@pytest.fixture(scope="module")
def probes():
    """(checkpoint, probes with the reference's own greedy tokens, its logprobs)."""
    hf = {k: v for k, v in CONF.items() if k not in run.OWN_KEYS}
    ckpt, *_ = checkpoint.ensure_checkpoint(run.HERE / ".cache", "command-a-plus-ep8", hf, SEED, plan)
    seqs = [_draw.token_ids(SEED, 800_000 + i, n, CONF["vocab_size"])
            for i, n in enumerate(run.PROBE_LENGTHS)]
    lengths = [len(s) for s in seqs]
    for _ in range(run.PROBE_TOKENS):  # greedy, one forward pass a token (no cache)
        last = R.forward_logits(ckpt, seqs, [(len(s) - 1, len(s)) for s in seqs])
        seqs = [s + [int(np.argmax(row[0]))] for s, row in zip(seqs, last)]
    asked = [{"tokens": s, "prompt_len": n} for s, n in zip(seqs, lengths)]
    return ckpt, asked, R.teacher_forced_logprobs(ckpt, asked)


def worst(a, b):
    return max(abs(x - y) for p, q in zip(a, b) for x, y in zip(p, q))


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_reads_as_not_correct(probes, control):
    ckpt, asked, healthy = probes
    got = worst(R.teacher_forced_logprobs(ckpt, asked, CONTROLS[control]), healthy)
    correct = got <= ATOL  # run.py's comparison
    print(f"control {control} seed {SEED}: worst |logprob - reference| {got:.4f} "
          f"(tolerance {ATOL}): correct {correct}")
    if control not in BELOW_IS_FINE:
        assert not correct, f"{control} reads {got:.4f}, inside the tolerance {ATOL}"
