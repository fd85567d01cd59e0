"""Device: peak device memory over the chip's HBM:
`dynamo_engine_hbm_bytes{kind="peak"}` / `peaks.json` hbm_bytes."""
import probe


def read(ctx):
    peak = probe.sample(ctx["m1"], "dynamo_engine_hbm_bytes", kind="peak")
    if not peak or not ctx.get("peaks"):
        return None
    return 100.0 * peak / ctx["peaks"]["hbm_bytes"]
