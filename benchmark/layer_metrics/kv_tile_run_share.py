"""Cache manager: of the tiles of pages that running sequences hold (what the
decode attention kernel walks at a time), the share whose pages are one run
of the pool, which the kernel fetches in one copy a pool where it pays a copy
a page for any other: `dynamo_engine_kv_tiles` run / (run + scattered), over
the window's 1 Hz samples taken together. A program without the counter (the
parent of the PR that added it) reports nothing."""
import probe


def read(ctx):
    run = scattered = 0.0
    for _, table in ctx["samples"]:
        r = probe.sample(table, "dynamo_engine_kv_tiles", state="run")
        s = probe.sample(table, "dynamo_engine_kv_tiles", state="scattered")
        if r is not None and s is not None:
            run += r
            scattered += s
    return 100.0 * run / (run + scattered) if run + scattered else None
