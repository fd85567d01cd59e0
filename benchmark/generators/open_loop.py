"""Generator `open_loop`: independent users. Requests are due on a schedule
fixed before the run, whatever the server does; each has a fresh prompt (no
two share a block) and asks for a fixed number of tokens.

Mix parameters (`benchmark/traffic/<mix>.json`):
  arrival      "poisson": exponential gaps at the cell's `rate_rps`
  prompt       {"median", "sigma", "min", "max"}: lognormal prompt lengths
  output       the same for the tokens asked
  order_seed   fixes the one order of gaps and lengths; the run's seed
               rotates it and draws the token ids
  lead_in_s    seconds of the same traffic before the window opens, so that
               the window starts on a loaded system; those requests are not
               `attempted`, but tokens they complete inside the window count
               in the rate
The cell file gives `rate_rps`. A sweep passes `steps`: [(rate, seconds)].
"""

from __future__ import annotations

import asyncio
import random
import time

from generators import _draw


def build(mix: dict, cell: dict, vocab: int, seed: int, seconds: float, steps: list | None = None) -> dict:
    if mix.get("arrival", "poisson") != "poisson":
        raise ValueError(f"open_loop knows poisson arrivals, not {mix['arrival']!r}")
    rng = random.Random(int(mix.get("order_seed", 0)))
    lead = float(mix.get("lead_in_s", 0.0))
    if steps is None:
        steps = [(float(cell["rate_rps"]), lead + seconds)]
        offset = -lead
    else:
        offset = 0.0
    items, at = [], offset
    for rate, duration in steps:
        n = max(1, round(rate * duration))
        gaps = _draw.shuffled(_draw.exponential_gaps(n, rate), rng)
        scale = duration / sum(gaps)  # the step offers exactly n requests in `duration`
        prompts = _draw.shuffled(_draw.lognormal_set(n, mix["prompt"]), rng)
        outputs = _draw.shuffled(_draw.lognormal_set(n, mix["output"]), rng)
        t = at
        for g, p, o in _draw.rotated(list(zip(gaps, prompts, outputs)), seed):
            t += g * scale
            items.append({"due": t, "prompt_len": p, "max_tokens": o, "step": rate})
        at += duration
    return {"items": items, "seed": seed, "vocab": vocab, "end": at,
            "max_context": mix["prompt"]["max"] + mix["output"]["max"]}


def drive(plan: dict, send, t_open: float, t_close: float) -> list:
    """One task per item, which sleeps until the item is due and sends it.
    `send(prompt, max_tokens, due, tag)` is a coroutine giving an Outcome;
    the caller awaits the tasks, or cancels them when the run is over."""

    async def one(i: int, item: dict):
        due = t_open + item["due"]
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        prompt = _draw.token_ids(plan["seed"], i, item["prompt_len"], plan["vocab"])
        return await send(prompt, item["max_tokens"], due, f"{item['step']:g}")

    return [asyncio.create_task(one(i, it)) for i, it in enumerate(plan["items"])]
