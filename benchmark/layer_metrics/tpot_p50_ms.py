"""Benchmark client: median over the window's requests that succeeded of
(last token time - first token time) / (tokens - 1): the stream a user of a
closed-loop cell reads at, prefill chunks of other sessions included. Beside
`ttft_mean_ms.cmda` it is a turn's length: in a closed loop a session's next
turn waits for this one's answer, so both move what the cell completes. Not an
end-to-end metric there: `command-a-plus-ep8.doc-sessions` read its 95th
percentile at 40.7-47.5 ms over 14 runs of one tree (my chip runs, PR 37), a
spread of more than half the 6% bound."""
import client
from layer_metrics import _common


def read(ctx):
    tpot = [o.tpot_s * 1e3 for o in _common.in_window(ctx) if o.ok and o.tpot_s is not None]
    return client.percentile(tpot, 50) if tpot else None
