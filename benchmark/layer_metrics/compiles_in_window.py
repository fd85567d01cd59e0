"""Runner: jit-cache growths the program counted inside the window
(`dynamo_engine_xla_compiles_total`). Has to be 0: a run with a compile in
the window is reported `correct: false`."""
from layer_metrics import _common


def read(ctx):
    return _common.delta(ctx, "dynamo_engine_xla_compiles_total")
