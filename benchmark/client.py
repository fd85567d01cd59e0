"""The streaming client: one request over `/v1/completions` with a token-id
prompt and `ext.ignore_eos`, timed on this process's monotonic clock.

Copied from `dynamo_tpu/loadgen/replay.py` `replay_http`, so that a later PR
may change the program's replay and not the yardstick. Corrected:

- time to first token runs from when the request was DUE, not from when it
  was sent: in an open loop a late generator or a stalled server delays later
  requests too, and that wait is the user's (the original only logged the lag);
- output tokens are read from the final chunk's `usage.completion_tokens`,
  not counted as SSE chunks: the engine sends one chunk per decode window
  (8 tokens), so chunk gaps are not token gaps. Time per output token is
  (last token time - first token time) / (tokens - 1) per request;
- a request that ends with another status than 200, an error event, another
  token count than asked, or no `[DONE]` is a failure, with its reason.
"""

from __future__ import annotations

import dataclasses
import json
import time


@dataclasses.dataclass
class Outcome:
    due: float  # monotonic seconds: when the request was to be sent
    sent: float = 0.0
    first: float | None = None  # first chunk that carried text
    last: float | None = None  # last chunk that carried text
    done: float | None = None
    prompt_tokens: int = 0
    asked_tokens: int = 0
    output_tokens: int = 0  # from usage
    chunks: int = 0
    error: str = ""
    logprobs: list = dataclasses.field(default_factory=list)
    tag: str = ""

    @property
    def ok(self) -> bool:
        return not self.error

    @property
    def lag_s(self) -> float:
        return self.sent - self.due

    @property
    def ttft_s(self) -> float | None:
        return None if self.first is None else self.first - self.due

    @property
    def tpot_s(self) -> float | None:
        """Seconds per output token after the first."""
        if self.first is None or self.last is None or self.output_tokens < 2:
            return None
        return (self.last - self.first) / (self.output_tokens - 1)


async def consume_sse(lines, out: Outcome, now=time.monotonic, keep: bool = False) -> None:
    """Read an SSE body (an async iterator of byte lines) into `out`."""
    finished = done = False
    async for raw in lines:
        line = raw.decode("utf-8", "replace").rstrip("\r\n")
        if not line.startswith("data:"):
            continue  # separators, comments, named events
        payload = line[5:].strip()
        if payload == "[DONE]":
            done = True
            break
        t = now()
        doc = json.loads(payload)
        if not isinstance(doc, dict):
            continue
        if "error" in doc:
            out.error = out.error or f"error event: {str(doc['error'])[:120]}"
            continue
        out.chunks += 1
        choice = (doc.get("choices") or [{}])[0]
        text = choice.get("text") or ""
        if text:
            if out.first is None:
                out.first = t
            out.last = t
        if keep and choice.get("logprobs"):
            lp = choice["logprobs"]
            out.logprobs.extend(zip(lp.get("tokens") or [], lp.get("token_logprobs") or []))
        usage = doc.get("usage")
        if usage:
            out.output_tokens = int(usage.get("completion_tokens") or 0)
            out.prompt_tokens = int(usage.get("prompt_tokens") or out.prompt_tokens)
        if choice.get("finish_reason"):
            finished = True
            if choice["finish_reason"] != "length":
                out.error = out.error or f"finish_reason {choice['finish_reason']}"
    out.done = now()
    if out.error:
        return
    if not (finished and done):
        out.error = "stream ended early"
    elif out.output_tokens != out.asked_tokens:
        out.error = f"{out.output_tokens} tokens, {out.asked_tokens} asked"
    elif out.first is None:
        out.error = "no text chunk"


async def complete(session, base: str, model: str, prompt: list, max_tokens: int,
                   due: float, tag: str = "", logprobs: bool = False,
                   sink: list | None = None, now=time.monotonic) -> Outcome:
    """Send one streaming completion (the caller has slept until `due`). The
    outcome joins `sink` before anything is sent, so that a request cut by
    the end of the run is still counted: it has no `done`."""
    out = Outcome(due=due, prompt_tokens=len(prompt), asked_tokens=max_tokens, tag=tag)
    if sink is not None:
        sink.append(out)
    body = {"model": model, "prompt": prompt, "stream": True, "max_tokens": max_tokens,
            "temperature": 0.0, "ext": {"ignore_eos": True}}
    if logprobs:
        body["logprobs"] = 0
    out.sent = now()
    try:
        async with session.post(f"{base}/v1/completions", json=body) as resp:
            if resp.status != 200:
                out.error = f"http {resp.status}"
                await resp.read()
                out.done = now()
            else:
                await consume_sse(resp.content, out, now=now, keep=logprobs)
    except Exception as e:  # a failed request is a counted outcome, not a crash
        out.error = out.error or f"{type(e).__name__}: {e}"
        out.done = now()
    return out


def percentile(values: list, q: float) -> float:
    """Linear interpolation between order statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
