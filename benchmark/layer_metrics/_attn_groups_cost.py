"""Bytes one decode attention call of ONE layer has to move in a model whose
attention layers keep different tokens (layer groups: a sliding window beside
full attention), from shapes alone. Kept with the benchmark and beside its
readers, not in `costs.py`, which is a file that was here before the groups.
"""

from __future__ import annotations

import re

import probe

BF16 = 2
PAGE_SIZE = 16


def group_decode_attention_bytes(config: dict, pages_per_layer: float, batch: float,
                                 dtype_bytes: int = BF16) -> float:
    """What ONE call (one layer of the group, one decode step) has to read and
    write at the least: the K and V of every page the decoding sequences hold
    in that layer once, and a query and an output row per sequence and head.
    A window layer's pages are what its sequences have not given back (the
    window and the page the newest token lies in); nothing behind the window
    is counted, since the kernel does not have to read it."""
    hd = config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]
    kv = pages_per_layer * PAGE_SIZE * 2 * config["num_key_value_heads"] * hd * dtype_bytes
    qo = 2 * batch * config["num_attention_heads"] * hd * dtype_bytes
    return kv + qo


def group_roofline(ctx: dict, group: str, sliding: bool):
    """Share of the HBM roofline of the decode attention calls of one group:
    the calls whose name on the device's operation line has `sliding` in it,
    or the other decode attention calls. The pages are the program's gauge
    `dynamo_engine_kv_group_pages{group, state="decoding"}` (pages the last
    decode window's sequences held, all layers of the group together),
    sampled once a second; the batch is the mean of the window's decode
    records. None where the program has no such gauge or the trace no such
    call."""
    t, conf = ctx.get("trace"), ctx.get("config") or {}
    kinds = conf.get("layer_types")
    if not t or not ctx.get("peaks") or not kinds:
        return None
    layers = sum(1 for k in kinds if (k == "sliding_attention") == sliding)
    decode = re.compile(r"paged_decode_attention", re.I)
    names = [n for n in t["ops_by_name"] if decode.search(n) and ("sliding" in n.lower()) == sliding]
    calls = sum(t["calls_by_name"][n] for n in names)
    seconds = sum(t["ops_by_name"][n] for n in names)
    pages = [probe.sample(table, "dynamo_engine_kv_group_pages", group=group, state="decoding")
             for _, table in ctx["samples"]]
    pages = [p for p in pages if p]
    windows = [r["participants"] for r in ctx["records"] if r["kind"] == "decode_window"]
    if not layers or not calls or seconds <= 0 or not pages or not windows:
        return None
    need = group_decode_attention_bytes(conf, sum(pages) / len(pages) / layers,
                                        sum(windows) / len(windows))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (seconds / calls)
