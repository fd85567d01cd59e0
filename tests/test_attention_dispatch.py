"""The attention dispatch's choice table (ops/attention.py): one kernel per
shape class, chosen from what the code can observe (platform, pool rank,
dtype, head geometry, page size, mesh) and from no environment variable.

Trace only (``jax.eval_shape``): nothing runs, so every row costs
milliseconds. ``_on_tpu`` is patched to true so the SHAPE rule decides, as
on the chip (``DYNTPU_PALLAS=1`` would force the kernels on whatever the
shape). Each row is traced twice, the second time with the two variables
that used to choose a kernel set to a non-default value: the logged path
must not move."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dynamo_tpu.ops import attention
from dynamo_tpu.quant.kv import QuantizedPages

S = jax.ShapeDtypeStruct


def _pool(pages, ps, hkv, d, *, int8=False, folded=False):
    shape = (pages, ps, hkv * d) if folded else (pages, ps, hkv, d)
    if int8:
        return QuantizedPages(S(shape, jnp.int8), S(shape[:2], jnp.float32))
    return S(shape, jnp.bfloat16)


def _decode(hq, hkv, d, ps, **pool):
    k = _pool(64, ps, hkv, d, **pool)
    return (S((4, hq, d), jnp.bfloat16), k, k, S((4, 8), jnp.int32), S((4,), jnp.int32))


def _prefill(T, hq, hkv, d, ps, width=8, **pool):
    k = _pool(64, ps, hkv, d, **pool)
    return (S((T, hq, d), jnp.bfloat16), k, k, S((width,), jnp.int32), S((T,), jnp.int32))


LOOKAHEAD = "pallas:paged_decode_attention_pallas_lookahead"
FOLDED = "pallas:paged_decode_attention_pallas_folded"

#: id: op, arguments, tp, what the logged path must contain, and the reason
TABLE = [
    pytest.param("decode", _decode(16, 2, 128, 16), 1,
        [LOOKAHEAD, "tile=8x16 window=2"], "Hkv=2 D=128 ps=16", id="decode-bf16-d128-ps16"),
    pytest.param("decode", _decode(28, 4, 128, 16, int8=True), 1,
        [LOOKAHEAD, "tile=8x16 window=2"], "", id="decode-int8-d128-ps16"),
    pytest.param("decode", _decode(32, 8, 128, 128), 1,
        [LOOKAHEAD, "tile=1x128 window=4"], "", id="decode-bf16-d128-ps128"),
    # 32 kv heads at page 128: four tiles of 2 MiB overrun the 6 MiB budget
    pytest.param("decode", _decode(32, 32, 128, 128), 1,
        [LOOKAHEAD, "window=0:perseq"], "", id="decode-window-0"),
    # folded pools take the same walk, the folded row of Hkv * D lanes one head
    pytest.param("decode", _decode(32, 4, 64, 16, folded=True), 1,
        [FOLDED, "tile=8x16 window=2"], "D=64", id="decode-folded-d64"),
    pytest.param("decode", _decode(32, 4, 64, 16), 1,
        [FOLDED, "tile=8x16 window=2"], "", id="decode-d64-unfolded-pool"),
    pytest.param("decode", _decode(32, 8, 64, 16, folded=True), 1,
        [FOLDED, "tile=8x16 window=2"], "Hkv=8 D=64", id="decode-folded-lfm2"),
    pytest.param("decode", _decode(32, 8, 64, 16, int8=True, folded=True), 1,
        [FOLDED, "tile=8x16 window=2"], "", id="decode-folded-int8"),
    pytest.param("decode", _decode(32, 8, 64, 128, folded=True), 1,
        [FOLDED, "tile=1x128 window=4"], "", id="decode-folded-ps128"),
    # one kv head of 128 a shard: 128 folded lanes each
    pytest.param("decode", _decode(28, 4, 128, 16, folded=True), 4,
        [FOLDED, "tile=8x16 window=2", "shard_map tp=4"], "", id="decode-folded-tp4-one-kv-head"),
    # a page of 128 tokens by 4096 lanes: four of them overrun the budget
    pytest.param("decode", _decode(64, 64, 64, 128, folded=True), 1,
        ["reference"], "no tile of the folded pool fits VMEM", id="decode-folded-window-0"),
    pytest.param("decode", _decode(4, 2, 80, 16), 1,
        ["reference"], "no Pallas kernel for this backend/shape", id="decode-d80-not-lane-aligned"),
    pytest.param("decode", _decode(16, 8, 128, 16), 4,
        [LOOKAHEAD, "tile=8x16", "shard_map tp=4"], "", id="decode-tp4"),
    pytest.param("prefill", _prefill(256, 16, 2, 128, 16), 1,
        ["pallas:lookahead", "tile=128"], "T=256", id="prefill-t256"),
    # the ladder of table widths: 128 pages of 16 keep the tile of 128 tokens
    # and its cross-program window, a wider table takes 512 and the basic kernel
    pytest.param("prefill", _prefill(512, 16, 2, 128, 16, width=128), 1,
        ["pallas:lookahead", "tile=128"], "", id="prefill-table-128-pages"),
    pytest.param("prefill", _prefill(512, 16, 2, 128, 16, width=256), 1,
        ["pallas:basic", "tile=512"], "", id="prefill-table-256-pages"),
    pytest.param("prefill", _prefill(512, 128, 8, 128, 16, width=1024), 1,
        ["pallas:basic", "block_q=32", "tile=512"], "", id="prefill-table-1024-pages-128-heads"),
    pytest.param("prefill", _prefill(512, 28, 4, 128, 16, width=512, int8=True), 1,
        ["pallas:basic", "tile=512"], "", id="prefill-table-512-pages-int8"),
    pytest.param("prefill", _prefill(128, 28, 4, 128, 16, int8=True), 1,
        ["pallas:lookahead"], "", id="prefill-int8"),
    pytest.param("prefill", _prefill(64, 16, 2, 128, 16), 1,
        ["reference"], "not a multiple of block_q=128", id="prefill-t64-d128"),
    pytest.param("prefill", _prefill(64, 32, 4, 64, 16, folded=True), 1,
        ["pallas:folded"], "", id="prefill-folded"),
    # 16 kv heads of 256 lanes at page 256: one tile is 4 MiB of an 8 MiB budget
    pytest.param("prefill", _prefill(128, 16, 16, 256, 256), 1,
        ["pallas:basic"], "", id="prefill-window-0"),
    pytest.param("prefill", _prefill(128, 16, 8, 128, 16), 4,
        ["pallas:lookahead", "shard_map tp=4"], "", id="prefill-tp4"),
    pytest.param("prefill", _prefill(128, 16, 8, 128, 16, width=512), 4,
        ["pallas:basic", "tile=512", "shard_map tp=4"], "", id="prefill-tp4-table-512-pages"),
]


@pytest.mark.parametrize("op, args, tp, path_has, why_has", TABLE)
def test_dispatch_chooses_from_shapes_alone(monkeypatch, op, args, tp, path_has, why_has):
    monkeypatch.delenv("DYNTPU_PALLAS", raising=False)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    seen = []
    monkeypatch.setattr(attention, "_log_path", lambda *a: seen.append(a))
    fn = {"decode": attention.dispatch_paged_decode_attention,
          "prefill": attention.dispatch_paged_prefill_attention}[op]
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",)) if tp > 1 else None

    def trace():
        out = jax.eval_shape(lambda *a: fn(*a, mesh=mesh), *args)
        assert out.shape == args[0].shape and out.dtype == args[0].dtype

    trace()
    monkeypatch.setenv("DYNTPU_DECODE_KERNEL", "chunked")
    monkeypatch.setenv("DYNTPU_PREFILL_KERNEL", "basic")
    trace()
    assert len(seen) == 2 and seen[0] == seen[1], seen
    got_op, path, why = seen[0]
    assert got_op == op
    assert "interpret" not in path
    for piece in path_has:
        assert piece in path, (path, why)
    assert why_has in why, (path, why)


def test_prefill_dispatch_labels_the_tile_by_table_width(monkeypatch):
    """The scheduler's prefill dispatch span reads the tile from
    `attention.prefill_tiles`, keyed by the width it dispatches."""
    monkeypatch.delenv("DYNTPU_PALLAS", raising=False)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "prefill_tiles", {})
    for width in (128, 1024):
        jax.eval_shape(attention.dispatch_paged_prefill_attention,
                       *_prefill(512, 16, 2, 128, 16, width=width))
    assert attention.prefill_tiles == {128: 128, 1024: 512}
