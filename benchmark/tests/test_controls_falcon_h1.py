"""What `falcon-h1-34b-d6`'s logprob tolerance can tell from the served model, at
FULL WIDTH on the CPU, by the plain reference alone: the harness's 4 probes x 8
greedy tokens, computed once as the reference computes them and once per
control. A control stands for a served run of another model or in a lower
precision, so it has to come out as not correct by the harness's own
comparison (`run.py` `run_cell`: `worst <= logprob_atol`): its worst
|logprob - reference| over the tolerance. The published multipliers make the
logits 0.011 wide under the writer's 0.02-normal matrices (every logprob is
-12.47 give or take a few hundredths), so the tolerance and every reading here
are in the thousandths.

  no_rope            no rotary embedding
  position_skew      positions from the hand-off on are one too high (a decode
                     that counts from the wrong place)
  no_key_multiplier  `key_multiplier` left out (1 for 0.011)
  no_attention       the attention branch dropped from every block
  state_lost         the decode steps start from a Mamba state of zeros (a
                     state not handed from prefill to decode)
  conv_window_late   they start from the convolution window of a position
                     earlier (a hand-off that stops one token early)
  no_c_multiplier    C's segment of `ssm_multipliers` left out (1 for 0.5)
  fp8_weights        every matrix in float8 e4m3, the nearest floating
                     precision below the checkpoint's bf16, one scale per
                     output channel
  int8               every matrix in int8, one scale per output channel

By hand and by name (`FALCON_H1_CONTROLS=1 pytest benchmark/tests/test_controls_falcon_h1.py -s
--basetemp=/root/scratch/tmp`): it writes the 10.5 GB checkpoint into the
test's temporary directory and makes 18 passes of about a minute on 8 cores.
`FALCON_H1_CONTROLS_SEED` picks the weights. `tests/test_falcon_h1.py` holds
the same controls at small size in tier-1.
"""

import json
import os

import numpy as np
import pytest

import checkpoint
import run
from checkpoints import falcon_h1 as plan
from generators import _draw
from reference import falcon_h1 as R

pytestmark = pytest.mark.skipif(
    os.environ.get("FALCON_H1_CONTROLS") != "1",
    reason="full width: 10.5 GB of weights and some twenty minutes; ask by FALCON_H1_CONTROLS=1")

CONF = json.loads((run.HERE / "configs/falcon-h1-34b-d6.json").read_text())
ATOL = float(CONF["benchmark"]["logprob_atol"])
SEED = int(os.environ.get("FALCON_H1_CONTROLS_SEED", 2147498845))

CONTROLS = {
    "no_rope": {"rope": False},
    "position_skew": {"decode_position_skew": 1},
    "no_key_multiplier": {"without": ["key_multiplier"]},
    "no_attention": {"attention": False},
    "state_lost": {"state": "lost"},
    "conv_window_late": {"conv_window": "late"},
    "no_c_multiplier": {"without": ["ssm_multipliers.3"]},
    "fp8_weights": {"quant": "fp8"},
    "int8": {"quant": "int8"},
}
#: what the tolerance CANNOT tell from the served model (`logprob_atol_why`
#: says why for each, PERF.md section 7 which edit to `checkpoint.py` would
#: let a plan draw them larger): held to read INSIDE the tolerance, so that
#: the file's statement and this list stay true together
CANNOT_TELL = {"int8"}


@pytest.fixture(scope="module")
def probes(tmp_path_factory):
    """(checkpoint, probes with the reference's own greedy tokens, its logprobs)."""
    hf = {k: v for k, v in CONF.items() if k not in run.OWN_KEYS}
    cache = tmp_path_factory.mktemp("falcon_h1_controls")
    ckpt, *_ = checkpoint.ensure_checkpoint(cache, "falcon-h1-34b-d6", hf, SEED, plan)
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
    print(f"control {control} seed {SEED}: worst |logprob - reference| {got:.6f} "
          f"(tolerance {ATOL}): correct {correct}")
    if control in CANNOT_TELL:
        assert correct, (f"{control} reads {got:.6f}, over the tolerance {ATOL}: the tolerance CAN "
                         "tell it now; take it off CANNOT_TELL and out of `logprob_atol_why`")
    else:
        assert not correct, f"{control} reads {got:.6f}, inside the tolerance {ATOL}"
