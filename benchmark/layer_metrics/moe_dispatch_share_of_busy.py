"""Kernels: the dispatch AROUND the expert products, which
`moe_share_of_busy` says it leaves out: leaf seconds of the parts
`moe_router` and `moe_dispatch`, and of whatever runs under `moe_experts`
that is not a product (`moe_grouped_matmul*` / `ragged-dot*`: the activation
between the products), over busy seconds (`trace_parts.py`)."""
from layer_metrics import _parts


def read(ctx):
    t = _parts.parts(ctx)
    if not t:
        return None
    between = sum(s for name, s in t["ops_by_part"].get("moe_experts", {}).items()
                  if not _parts.EXPERT_PRODUCT.search(name))
    return _parts.share(ctx, _parts.seconds(t, ("moe_router", "moe_dispatch")) + between)
