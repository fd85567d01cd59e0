"""The sweep's arithmetic: tokens delivered in an interval, and when a step
counts as sustained."""

import client
import run


def _req(due, first, last, n, done=None, ok=True):
    return client.Outcome(due=due, sent=due, first=first, last=last, done=done or last,
                          output_tokens=n, asked_tokens=n, error="" if ok else "http 503")


def test_delivered_tokens_splits_a_stream_over_intervals():
    o = _req(0.0, 1.0, 5.0, 41)
    assert run.delivered_tokens([o], 0, 3) == 21.0  # the first token, then 10 a second
    assert run.delivered_tokens([o], 3, 10) == 20.0
    assert run.delivered_tokens([_req(0, 1, 5, 41, ok=False)], 0, 10) == 0.0


def test_a_step_is_sustained_until_the_backlog_grows():
    # step 1: 1 request/s, each streams 11 tokens over a second right away
    outs = [_req(float(i), i + 0.1, i + 1.1, 11) for i in range(20)]
    # step 2: 4 requests/s, but the server still finishes one a second
    outs += [_req(20 + i / 4, 20.1 + i, 21.1 + i, 11) for i in range(80)]
    table = run.sweep_table({"t_open": 0.0, "outcomes": outs}, [(1.0, 20.0), (4.0, 20.0)])
    assert table[0]["sustained"] and table[0]["requests"] == 20
    assert abs(table[0]["delivered_tokens_per_s"] - 11.0) < 0.2
    assert not table[1]["sustained"] and table[1]["in_flight_end"] > table[1]["in_flight_mid"]
