"""Generator `sessions`: agents and assistants. A fixed number of sessions
run at once; within a session the loop is closed (the next turn is due
`think_s` after the last answer ended), and a session that has had all its
turns is replaced by a new one. Every session opens with one of a few long
system prompts; each turn appends a tail of fresh tokens (standing for the
last answer and the next question) to the session's prompt so far and asks
for an answer. So each prompt but a session's first extends a prompt the
server has seen: the prefix cache, the page allocator and decode attention
over long contexts do the work.

Sessions start at staggered ages, as they would be found in a running
system: a session of age k has had k turns, and its history is sent once in
`prepare` (one token asked), which is set-up the traffic needs.

Mix parameters (`benchmark/traffic/<mix>.json`):
  sessions         how many at once (a cell file may override it)
  system_prompts   how many distinct ones; system_len tokens each
  turns            turns in a session
  tail             {"min", "max"}: fresh tokens a turn appends
  output           {"min", "max"}: tokens a turn asks for
  think_s          seconds between an answer's end and the next turn
The same cycle of tails and answers serves every seed, from another start.
"""

from __future__ import annotations

import asyncio
import random
import time

from generators import _draw

#: turn slots drawn per session slot: enough for any window up to 51 s
_TURNS_PER_SLOT = 256


def build(mix: dict, cell: dict, vocab: int, seed: int, seconds: float) -> dict:
    rng = random.Random(int(mix.get("order_seed", 0)))
    n = int(cell.get("sessions", mix["sessions"]))
    per = _TURNS_PER_SLOT
    tails = _draw.rotated(_draw.shuffled(
        _draw.uniform_set(n * per, mix["tail"]["min"], mix["tail"]["max"]), rng), seed)
    outs = _draw.rotated(_draw.shuffled(
        _draw.uniform_set(n * per, mix["output"]["min"], mix["output"]["max"]), rng), seed)
    rng = random.Random(seed)
    turns = int(mix["turns"])
    slots = []
    for s in range(n):
        slots.append({
            "age": s % turns,  # staggered: every age equally often
            "first_system": rng.randrange(mix["system_prompts"]),
            "tails": tails[s * per:(s + 1) * per],
            "outputs": outs[s * per:(s + 1) * per],
            "systems": [rng.randrange(mix["system_prompts"]) for _ in range(per)],
        })
    return {"slots": slots, "seed": seed, "vocab": vocab, "mix": mix, "end": seconds,
            "max_context": mix["system_len"] + turns * (mix["tail"]["max"] + mix["output"]["max"])}


def _system(plan: dict, which: int) -> list:
    return _draw.token_ids(plan["seed"], 700_000 + which, plan["mix"]["system_len"], plan["vocab"])


def _history(plan: dict, s: int) -> tuple:
    """(prompt so far, turn slots used) of slot s's first session at its age."""
    slot = plan["slots"][s]
    prompt = _system(plan, slot["first_system"])
    for k in range(slot["age"]):
        prompt = prompt + _draw.token_ids(plan["seed"], s * 1000 + k, slot["tails"][k], plan["vocab"])
    return prompt, slot["age"]


async def prepare(plan: dict, send) -> dict:
    """Put every starting session's history into the prefix cache."""
    t0 = time.monotonic()
    outs = []
    for s in range(len(plan["slots"])):
        prompt, _ = _history(plan, s)
        outs.append(await send(prompt, 1, time.monotonic(), "history"))
    bad = [o.error for o in outs if not o.ok]
    if bad:
        raise RuntimeError(f"history prefill failed: {bad[:3]}")
    return {"histories": len(outs), "history_tokens": sum(o.prompt_tokens for o in outs),
            "seconds": time.monotonic() - t0}


def drive(plan: dict, send, t_open: float, t_close: float) -> list:
    """One task per session slot. The first turns are spread over the first
    `think_s` after the lead-in begins, so that 32 sessions do not all speak
    at once."""
    mix = plan["mix"]
    turns, think = int(mix["turns"]), float(mix["think_s"])
    lead = float(mix.get("lead_in_s", 0.0))
    n = len(plan["slots"])

    async def session_slot(s: int):
        slot = plan["slots"][s]
        prompt, used = _history(plan, s)
        k, age = used, slot["age"]
        due = t_open - lead + think * (s + 0.5) / n
        while due < t_close:
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            prompt = prompt + _draw.token_ids(plan["seed"], s * 1000 + k, slot["tails"][k], plan["vocab"])
            out = await send(prompt, slot["outputs"][k], due, f"turn{age}")
            k, age = k + 1, age + 1
            if age >= turns:  # the session is over: a new one takes its place
                prompt, age = _system(plan, slot["systems"][k]), 0
            due = (out.done or time.monotonic()) + think

    return [asyncio.create_task(session_slot(s)) for s in range(n)]
