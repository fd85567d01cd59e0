"""The corrected client's arithmetic on a scripted stream: time to first
token from when the request was DUE, tokens from `usage`, time per output
token over (tokens - 1), failures with their reason."""

import asyncio
import json

import client


def _line(text="", finish=None, usage=None, error=None):
    if error:
        return f"data: {json.dumps({'error': error})}\n".encode()
    doc = {"choices": [{"text": text, "finish_reason": finish}]}
    if usage is not None:
        doc["usage"] = {"prompt_tokens": 5, "completion_tokens": usage}
    return f"data: {json.dumps(doc)}\n".encode()


def _run(script, asked, due=100.0):
    """script: [(clock time at which the line is read, line)]."""
    clock = {"t": due}

    async def lines():
        for t, ln in script:
            clock["t"] = t
            yield ln

    out = client.Outcome(due=due, asked_tokens=asked, sent=due + 0.25)
    asyncio.run(client.consume_sse(lines(), out, now=lambda: clock["t"]))
    return out


def test_ttft_from_due_and_tokens_from_usage():
    # one token from the prefill, then two decode windows of 8 tokens each:
    # 3 chunks, 17 tokens
    out = _run([
        (100.40, b": comment\n"), (100.40, _line("t5")), (100.56, _line("t1 " * 8)),
        (100.72, _line("t2 " * 8)), (100.72, _line("", finish="length", usage=17)),
        (100.73, b"data: [DONE]\n"),
    ], asked=17)
    assert out.ok, out.error
    assert abs(out.lag_s - 0.25) < 1e-9
    assert abs(out.ttft_s - 0.40) < 1e-9  # from due (100.0), not from sent (100.25)
    assert out.output_tokens == 17 and out.chunks == 4
    assert abs(out.tpot_s - 0.32 / 16) < 1e-9  # not 0.16 a "token" as chunk gaps would say


def test_failures_have_reasons():
    assert "17 asked" in _run([(1, _line("a")), (2, _line("", "length", 9)), (2, b"data: [DONE]\n")], 17).error
    assert "early" in _run([(1, _line("a")), (2, _line("b"))], 2).error
    assert "error event" in _run([(1, _line(error={"message": "boom"}))], 2).error
    assert "finish_reason stop" in _run([(1, _line("a", "stop", 1)), (1, b"data: [DONE]\n")], 1).error
    out = _run([(1, _line("", "length", 3)), (1, b"data: [DONE]\n")], 3)
    assert out.error == "no text chunk" and out.ttft_s is None and out.tpot_s is None


def test_percentile_is_linear_interpolation():
    assert client.percentile([1, 2, 3, 4], 50) == 2.5
    assert client.percentile(list(range(101)), 95) == 95
