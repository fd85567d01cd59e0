"""A seed reorders the work and draws the token ids; it does not change the
amount of work."""

import json
from pathlib import Path

from generators import _draw, open_loop, sessions

BENCH = Path(__file__).resolve().parents[1]


def test_open_loop_same_sizes_for_every_seed():
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    a = open_loop.build(mix, {"rate_rps": 10.0}, 151936, 1, 30.0)
    b = open_loop.build(mix, {"rate_rps": 10.0}, 151936, 2**31 + 5, 30.0)
    again = open_loop.build(mix, {"rate_rps": 10.0}, 151936, 1, 30.0)
    assert a["items"] == again["items"]
    assert a["items"] != b["items"]
    for key in ("prompt_len", "max_tokens"):
        assert sorted(i[key] for i in a["items"]) == sorted(i[key] for i in b["items"])
    assert len(a["items"]) == 400  # 10/s over 10 s of lead-in and 30 s of window
    assert abs(a["items"][-1]["due"] - 30.0) < 1e-6 and a["items"][0]["due"] > -10.0
    lens = sorted(i["prompt_len"] for i in a["items"])
    assert lens[0] >= 32 and lens[-1] <= 2048 and 230 < lens[200] < 280  # median 256


def test_token_ids_skip_the_special_ids_and_repeat():
    ids = _draw.token_ids(2**31 + 7, 3, 1000, 151936)
    assert ids == _draw.token_ids(2**31 + 7, 3, 1000, 151936)
    assert min(ids) >= 3 and max(ids) < 151936 and ids != _draw.token_ids(1, 3, 1000, 151936)


def test_sessions_extend_their_own_prompts():
    mix = json.loads((BENCH / "traffic" / "sessions.json").read_text())
    plan = sessions.build(mix, {}, 151936, 9, 30.0)
    other = sessions.build(mix, {}, 151936, 10, 30.0)
    assert len(plan["slots"]) == 32
    assert sorted(t for s in plan["slots"] for t in s["tails"]) == \
        sorted(t for s in other["slots"] for t in s["tails"])
    assert sorted({s["age"] for s in plan["slots"]}) == list(range(8))
    prompt, used = sessions._history(plan, 5)  # age 5: system + 5 tails
    assert used == 5 and len(prompt) == 2048 + sum(plan["slots"][5]["tails"][:5])
    assert plan["max_context"] <= 8192
