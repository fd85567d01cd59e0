"""Ask the TPU's compiler, without a TPU, whether the serving kernels and steps
compile for one v5e chip (and a tp=4 step for a 2x2 mesh).

libtpu compiles for a chip that is described and not attached
(``jax.experimental.topologies``), so what Mosaic or XLA would refuse on the
chip is refused here, at no chip time: a DMA slice not aligned to the tiling,
a kernel that overruns scoped VMEM, a step that does not fit HBM, a kernel
that cannot be partitioned. Nothing runs, so this says nothing about results
or times — ``chip_smoke.py`` is the run.

The attention dispatchers ask ``jax.default_backend()``, which is still the
CPU here, so ``on_chip_dispatch()`` steers them the way the chip would
(kernels on, interpret off) around each lowering; the program itself has no
option for this.

    JAX_PLATFORMS=cpu python tools/tpu_compile.py            # every case
    JAX_PLATFORMS=cpu python tools/tpu_compile.py --steps    # whole steps only
    JAX_PLATFORMS=cpu python tools/tpu_compile.py --mosaic DIR   # the tier-1 kernel
        # cases' Mosaic modules as text without debug locations, one file a case:
        # run it before and after an edit to a kernel and `diff -r` the two
        # directories to see which programs the edit changed (PR 44)

``tests/test_tpu_compile.py`` runs ``kernel_cases(full=False)`` in tier-1.
"""

from __future__ import annotations

import contextlib
import functools
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from tools.make_hf_checkpoint import QWEN25_7B_GEOMETRY, TINYLLAMA_GEOMETRY  # noqa: E402

TOPOLOGY = "v5e:2x2"

#: published head geometries: (name, q heads, kv heads, head_dim)
GQA_GEOMETRIES = (
    ("tinyllama-1.1b", 32, 4, 64),  # folded pools
    ("qwen2.5-7b", 28, 4, 128),
    ("mixtral-8x7b", 32, 8, 128),
)
#: qwen2.5-3b, the benchmark's dense configuration
QWEN25_3B = ("qwen2.5-3b", 16, 2, 128)
#: command-a-plus-05-2026: 128 query heads over 8 kv heads, a window of 4096
COMMAND_A = ("command-a-plus", 128, 8, 128)
COMMAND_A_WINDOW = 4096
#: LFM2-8B-A1B: 32 query heads over 8 kv heads of 64, folded pools of 512 lanes
LFM2 = ("lfm2-8b-a1b", 32, 8, 64)
#: Falcon-H1-34B: 20 query heads over 4 kv heads of 128, five a group (the
#: first group that is no power of two)
FALCON_H1 = ("falcon-h1-34b", 20, 4, 128)
#: one tensor-parallel shard of qwen2.5-7b at tp=4: one kv head, which is under
#: Mosaic's sublane pack, so the pool is folded (LlamaModel.kv_folded), 128 lanes
TP4_SHARD = ("qwen2.5-7b-tp4-shard", 7, 1, 128)
#: DeepSeek-V2-Lite: 16 heads, kv_lora_rank 512 + rope 64, latent padded to 640
MLA_HEADS, MLA_DC, MLA_LATENT = 16, 512, 640


def topology():
    """The described v5e host; raises where libtpu cannot describe one."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name=TOPOLOGY)


@contextlib.contextmanager
def on_chip_dispatch():
    """Make the attention dispatchers choose what they choose on the chip,
    with the persistent compile cache off (an entry written for a described
    chip cannot be read back without one, and warns)."""
    from jax.experimental.compilation_cache import compilation_cache

    from dynamo_tpu.ops import attention

    real, attention._on_tpu = attention._on_tpu, lambda: True
    flag = os.environ.pop("DYNTPU_PALLAS", None)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        attention._on_tpu = real
        if flag is not None:
            os.environ["DYNTPU_PALLAS"] = flag
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@dataclass(frozen=True)
class Case:
    name: str
    build: Callable  # (struct) -> (fn, args); struct(shape, dtype) places on the chip


def _pools(S, num_pages, ps, hkv, d, int8: bool, folded: bool):
    from dynamo_tpu.quant.kv import QuantizedPages

    shape = (num_pages, ps, hkv * d) if folded else (num_pages, ps, hkv, d)
    if int8:
        return QuantizedPages(S(shape, jnp.int8), S((num_pages, ps), jnp.float32))
    return S(shape, jnp.bfloat16)


def _decode_case(geo, ps, int8, kernel=None, window=0, max_len=2048):
    name, hq, hkv, d = geo
    B, num_pages, max_pages = 16, 256, max_len // ps

    def build(S):
        from dynamo_tpu.ops import attention

        fn = kernel or attention.dispatch_paged_decode_attention
        if window:
            fn = functools.partial(fn, window=window)
        # LlamaModel.kv_folded's rule: head_dim under a lane row, or kv heads
        # that do not fill Mosaic's sublane pack (2 rows of bf16, 4 of int8)
        folded = d < 128 or hkv % (4 if int8 else 2) != 0
        return fn, (
            S((B, hq, d), jnp.bfloat16),
            _pools(S, num_pages, ps, hkv, d, int8, folded),
            _pools(S, num_pages, ps, hkv, d, int8, folded),
            S((B, max_pages), jnp.int32), S((B,), jnp.int32),
        )

    tag = (f"-{kernel.__name__}" if kernel else "") + (f"-window{window}" if window else "")
    return Case(f"decode{tag}-{name}-ps{ps}-{'int8' if int8 else 'bf16'}", build)


def _prefill_case(geo, ps, T, int8, lookahead=None, window=0, max_len=2048):
    name, hq, hkv, d = geo
    num_pages, max_pages = 256, max_len // ps

    def build(S):
        from dynamo_tpu.ops import attention
        from dynamo_tpu.ops.pallas.prefill_attention import paged_prefill_attention_pallas

        folded = d < 128
        if lookahead is None:
            fn = functools.partial(attention.dispatch_paged_prefill_attention, window=window)
        else:
            def fn(*a):
                return paged_prefill_attention_pallas(*a, lookahead=lookahead)
        return fn, (
            S((T, hq, d), jnp.bfloat16),
            _pools(S, num_pages, ps, hkv, d, int8, folded),
            _pools(S, num_pages, ps, hkv, d, int8, folded),
            S((max_pages,), jnp.int32), S((T,), jnp.int32),
        )

    tag = "" if lookahead is None else ("-lookahead" if lookahead else "-basic")
    tag += f"-window{window}" if window else ""
    tag += f"-table{max_len}" if max_len != 2048 else ""
    return Case(f"prefill{tag}-{name}-ps{ps}-T{T}-{'int8' if int8 else 'bf16'}", build)


def _mla_decode_case(ps):
    def build(S):
        from dynamo_tpu.ops.pallas.mla_attention import paged_mla_decode_attention_pallas

        def fn(*a):
            return paged_mla_decode_attention_pallas(*a, d_c=MLA_DC)
        # the model hands the kernels an f32 folded query (deepseek._fold_q)
        return fn, (
            S((16, MLA_HEADS, MLA_LATENT), jnp.float32),
            S((256, ps, MLA_LATENT), jnp.bfloat16),
            S((16, 2048 // ps), jnp.int32), S((16,), jnp.int32),
        )

    return Case(f"mla-decode-classic-ps{ps}", build)


def _mla_prefill_case(ps, T):
    def build(S):
        from dynamo_tpu.ops.pallas.mla_attention import paged_mla_prefill_attention_pallas

        def fn(*a):
            return paged_mla_prefill_attention_pallas(*a, d_c=MLA_DC)
        return fn, (
            S((T, MLA_HEADS, MLA_LATENT), jnp.float32),
            S((256, ps, MLA_LATENT), jnp.bfloat16),
            S((2048 // ps,), jnp.int32), S((T,), jnp.int32),
        )

    return Case(f"mla-prefill-ps{ps}-T{T}", build)


def _ssm_update_case(slots: int = 128, H: int = 128, P: int = 64, G: int = 8, N: int = 128):
    """The one-token state update at a published Mamba-2 shape (NemotronH's
    128 heads x 64 x 128 float32 a slot by default), one block's rows plus
    its trash row."""

    def build(S):
        from dynamo_tpu.ops.pallas.ssm_update import ssm_state_update_pallas

        return ssm_state_update_pallas, (
            S((slots + 1, H, P, N), jnp.float32), S((slots, H), jnp.float32),
            S((slots, H, P), jnp.float32), S((slots, G, N), jnp.float32),
            S((slots, G, N), jnp.float32), S((slots,), jnp.int32),
        )

    tag = "" if (H, P, G, N) == (128, 64, 8, 128) else f"-{H}x{P}x{N}g{G}"
    return Case(f"ssm-state-update-{slots}slots{tag}", build)


def parallel_cases() -> list[Case]:
    """`falcon-h1-34b-d6`: the unfolded decode and prefill kernels at 20 query /
    4 kv heads of 128 (five query heads a kv head), page 16, tables of 5120
    tokens, and the state update at 32 heads x 128 x 256 in 2 groups, 96 slots
    (a block of 16 heads: `head_block_for`)."""
    return [
        _decode_case(FALCON_H1, 16, False, max_len=5120),
        _prefill_case(FALCON_H1, 16, 128, False, max_len=5120),
        _prefill_case(FALCON_H1, 16, 1024, False, max_len=5120),
        _ssm_update_case(96, H=32, P=128, G=2, N=256),
    ]


def _grouped_matmul_case(name: str, M: int, K: int, N: int, held: int = 128):
    """The expert layer's grouped product over `held` experts' [K, N] matrices
    (bf16) and M static rows: `nemotron3-super-ep4` holds 128 (latent 1024,
    intermediate 2688, 22 rows a token), `command-a-plus-ep8` 16 of
    [4096, 4096] (32 MiB a matrix, walked in column blocks; 8 rows a token)."""

    def build(S):
        from dynamo_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas

        return grouped_matmul_pallas, (
            S((M, K), jnp.bfloat16), S((held, K, N), jnp.bfloat16), S((held,), jnp.int32),
        )

    return Case(f"moe-grouped-matmul-{name}", build)


#: a decode step of 128 slots (both banks) and a prefill pack of 2 x 512 rows
GROUPED_MATMUL_CASES = (
    ("decode-w1", 128 * 22, 1024, 2688), ("decode-w2", 128 * 22, 2688, 1024),
    ("prefill-w1", 1024 * 22, 1024, 2688),
)


def folded_cases() -> list[Case]:
    """`lfm2-8b-a1b-d16`: the folded decode and prefill kernels at 32 query / 8
    kv heads of 64 (512 folded lanes: the decode kernel walks tiles of 8 pages,
    16 DMAs of 16 KiB each, six tiles of scratch; the prefill kernel takes 32
    query rows a program, `folded_prefill_block_q`), page 16, tables of 5120
    tokens (the decode kernel on an int8 pool too, which no cell serves), and
    the grouped product at a bank of [32, 2048, 1792] and back (a decode step
    of 256 slots, a prefill pack of 1024 rows; 4 rows a token)."""
    return [
        _decode_case(LFM2, 16, False, max_len=5120),
        _decode_case(LFM2, 16, True, max_len=5120),
        _prefill_case(LFM2, 16, 128, False, max_len=5120),
        _prefill_case(LFM2, 16, 1024, False, max_len=5120),
        _grouped_matmul_case("lfm2-decode-w1", 256 * 4, 2048, 1792, held=32),
        _grouped_matmul_case("lfm2-decode-w2", 256 * 4, 1792, 2048, held=32),
        _grouped_matmul_case("lfm2-prefill-w1", 1024 * 4, 2048, 1792, held=32),
    ]


def window_cases() -> list[Case]:
    """`command-a-plus-ep8`: the window and the full decode and prefill kernels
    at 128 query / 8 kv heads of 128, page 16, tables of 16384 tokens (1024
    pages: the prefill kernels walk them in tiles of 512 tokens, 8 MiB of
    scores and as much of probabilities a program), and the grouped product
    at a bank of [16, 4096, 4096] (a decode step of 48 slots, a prefill pack
    of 1024 rows)."""
    W = COMMAND_A_WINDOW
    return [
        _decode_case(COMMAND_A, 16, False, window=W, max_len=16384),
        _decode_case(COMMAND_A, 16, False, max_len=16384),
        _prefill_case(COMMAND_A, 16, 512, False, window=W, max_len=16384),
        _prefill_case(COMMAND_A, 16, 512, False, max_len=16384),
        _prefill_case(COMMAND_A, 16, 64, False, window=W, max_len=16384),
        _grouped_matmul_case("command-a-decode", 48 * 8, 4096, 4096, held=16),
        _grouped_matmul_case("command-a-prefill", 1024 * 8, 4096, 4096, held=16),
    ]


def kernel_cases(full: bool) -> list[Case]:
    """Every Pallas kernel the default dispatch can reach, at published head
    geometries. ``full``: each page size (16 engine default, 64, 128) x each
    prefill bucket x bf16/int8; otherwise the tier-1 subset — one or two
    cases per kernel and geometry, the shapes the seed's kernels were refused
    at among them (marked)."""
    from dynamo_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas

    tiny, qwen, mixtral = GQA_GEOMETRIES
    bench = ("bench-16q8kv", 16, 8, 128)
    if full:
        cases = []
        for geo in (*GQA_GEOMETRIES, bench):
            for ps in (16, 64, 128):
                for int8 in (False, True):
                    cases.append(_decode_case(geo, ps, int8))
                    if geo is qwen:
                        cases.append(_decode_case(TP4_SHARD, ps, int8))
                    buckets = (64, 128, 256, 512, 1024) if geo[3] < 128 else (128, 256, 512, 1024)
                    cases += [_prefill_case(geo, ps, T, int8) for T in buckets]
        cases += [_prefill_case(QWEN25_3B, 16, 512, False, max_len=8192),
                  _prefill_case(mixtral, 16, 512, True, max_len=8192)]
        cases += [_prefill_case(mixtral, 16, 512, False, lookahead=False),
                  _decode_case(qwen, 16, False, kernel=paged_decode_attention_pallas),
                  _decode_case(qwen, 16, True, kernel=paged_decode_attention_pallas)]
        for ps in (16, 64, 128):
            cases.append(_mla_decode_case(ps))
            cases += [_mla_prefill_case(ps, T) for T in (128, 256, 512, 1024)]
        cases.append(_ssm_update_case())
        cases += [_grouped_matmul_case(*c) for c in GROUPED_MATMUL_CASES]
        cases.append(_grouped_matmul_case("prefill-w2", 1024 * 22, 2688, 1024))
        return cases + window_cases() + folded_cases() + parallel_cases()
    return [
        # decode: folded (256 lanes, and the 128 of one kv head a tp=4 shard),
        # lookahead, and the per-sequence kernel lookahead falls back to; int8
        # at page size < 128 was refused (scale-plane slice not aligned to the
        # 128 tiling)
        _decode_case(tiny, 16, False), _decode_case(tiny, 16, True),
        _decode_case(TP4_SHARD, 16, False), _decode_case(TP4_SHARD, 16, True),
        _decode_case(qwen, 16, False), _decode_case(qwen, 16, True),
        _decode_case(mixtral, 128, False), _decode_case(mixtral, 64, True),
        _decode_case(qwen, 16, True, kernel=paged_decode_attention_pallas),
        # folded flash prefill (TinyLlama): smallest and largest bucket
        _prefill_case(tiny, 16, 64, False), _prefill_case(tiny, 16, 1024, True),
        # lookahead flash prefill: refused at every head_dim-128 shape
        # (scoped VMEM 18-23 MiB against the 16 MiB default)
        _prefill_case(bench, 128, 512, False),
        _prefill_case(qwen, 16, 1024, False),
        # a table past 2048 tokens: the long context tile at 1024 rows a kv
        # head (qwen2.5-3b's 128 query rows a program, 8 heads a group), and
        # on an int8 pool (a [1, 512] scale row a tile)
        _prefill_case(QWEN25_3B, 16, 512, False, max_len=8192),
        _prefill_case(mixtral, 16, 512, True, max_len=8192),
        _prefill_case(mixtral, 16, 512, True),
        # the basic variant was refused at 32q/8kv too
        _prefill_case(mixtral, 16, 512, False, lookahead=False),
        # MLA: decode at the smallest and largest page; prefill refused with
        # the f32 query the model passes (17.3 MiB)
        _mla_decode_case(16), _mla_decode_case(128),
        _mla_prefill_case(16, 512),
        # the Mamba-2 state update, in place over the donated state
        _ssm_update_case(),
        # the expert layer's grouped product: whole-matrix blocks of 5.25 MiB,
        # double-buffered, need more scoped VMEM than the default 16 MiB
        *(_grouped_matmul_case(*c) for c in GROUPED_MATMUL_CASES),
        # command-a-plus-ep8: a window in both attention kernels at 128 query
        # heads (neither had run above 32), and a bank of [16, 4096, 4096]
        *window_cases(),
        # lfm2-8b-a1b-d16: the folded kernels at 512 lanes (the prefill one was
        # refused a place by the dispatcher at 64 rows x 32 heads) and the
        # grouped product at the two expert shapes
        *folded_cases(),
        # falcon-h1-34b-d6: five query heads a kv head in the unfolded kernels,
        # and the state update at four times NemotronH's state a head
        *parallel_cases(),
    ]


def _lower_case(case: Case, topo=None):
    """One case lowered for the first described chip (inside
    ``on_chip_dispatch()``)."""
    from jax.sharding import SingleDeviceSharding

    topo = topo or topology()
    chip = SingleDeviceSharding(topo.devices[0])

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    fn, args = case.build(S)
    return jax.jit(fn).lower(*args)


def compile_case(case: Case, topo=None):
    """Lower and compile one case for the first described chip; raises what
    the chip's compiler raises."""
    with on_chip_dispatch():
        return _lower_case(case, topo).compile()


def mosaic_text(case: Case, topo=None) -> str:
    """The case's program as lowered for the chip, with every Mosaic kernel's
    module (the custom call's ``body``: MLIR bytecode, base64) printed as text
    WITHOUT debug locations after it: a kernel file's line numbers are in the
    bytecode, so the lowered text itself differs after any edit above a
    kernel."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    with on_chip_dispatch():
        text = _lower_case(case, topo).as_text()
    body = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')
    out = [body.sub("BODY", text)]
    for blob in body.findall(text):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # the serialized dialect is versioned
        with ctx:
            out.append(ir.Module.parse(base64.b64decode(blob)).operation.get_asm(enable_debug_info=False))
    return "\n".join(out)


# ---------------- whole steps ----------------


def _llama_step_structs(geometry: dict, mesh, num_pages: int, page_size: int):
    """(model, params, kv) as ShapeDtypeStructs sharded the way ModelRunner
    shards them on ``mesh``; ``geometry`` is a config.json dict
    (tools/make_hf_checkpoint.py)."""
    from dynamo_tpu.models.llama import LlamaConfig, LlamaModel

    model = LlamaModel(LlamaConfig.from_hf_config(geometry))
    if mesh.shape.get("tp", 1) > 1:
        model.attn_mesh = mesh

    def place(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shardings
        )

    params = place(jax.eval_shape(model.init_params, jax.random.key(0)),
                   model.param_shardings(mesh))
    kv = place(jax.eval_shape(lambda: model.init_kv_cache(num_pages, page_size)),
               model.kv_cache_sharding(mesh))
    return model, params, kv


def compile_steps(geometry: dict, tp: int, num_pages: int, page_size: int = 16,
                  max_seqs: int = 16, lanes: int = 2, bucket: int = 512,
                  max_model_len: int = 2048, topo=None) -> dict:
    """Compile one decode step and one packed prefill step of a llama-family
    model on ``tp`` described chips; returns {step: compiled}."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    topo = topo or topology()
    mesh = Mesh(np.array(topo.devices[:tp]), ("tp",))
    rep = NamedSharding(mesh, P())
    mp = max_model_len // page_size

    def R(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    out = {}
    with on_chip_dispatch():
        model, params, kv = _llama_step_structs(geometry, mesh, num_pages, page_size)
        out["decode"] = jax.jit(model.decode, donate_argnums=(1,)).lower(
            params, kv, R((max_seqs,), jnp.int32), R((max_seqs,), jnp.int32),
            R((max_seqs, mp), jnp.int32), R((max_seqs,), jnp.bool_),
        ).compile()
        out["prefill_packed"] = jax.jit(model.prefill_packed, donate_argnums=(1,)).lower(
            params, kv, R((lanes, bucket), jnp.int32), R((lanes, bucket), jnp.int32),
            R((lanes, mp), jnp.int32), R((lanes, bucket), jnp.bool_), R((lanes,), jnp.int32),
        ).compile()
    return out


def compile_hybrid_steps(hf_config: dict, num_pages: int, max_seqs: int, page_size: int = 16,
                         lanes: int = 2, bucket: int = 512, max_model_len: int = 4096,
                         topo=None) -> dict:
    """Compile one decode step and one packed prefill step of a model with a
    per-slot state beside the page pools (models/nemotron_h.py: Mamba-2 state,
    the state-update kernel; models/lfm2_moe.py: a convolution window, folded
    pools; both the dropless expert dispatch) on one described chip;
    ``hf_config`` is a config.json dict. Returns {step: compiled}."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.models.registry import ARCHITECTURES, _resolve

    config_cls, model_cls, _ = _resolve(ARCHITECTURES[hf_config["architectures"][0]])

    one = SingleDeviceSharding((topo or topology()).devices[0])
    mp = max_model_len // page_size

    def R(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def place(tree):
        return jax.tree.map(lambda x: R(x.shape, x.dtype), tree)

    out = {}
    with on_chip_dispatch():
        model = model_cls(config_cls.from_hf_config(hf_config))
        params = place(jax.eval_shape(model.init_params, jax.random.key(0)))
        kv = place(jax.eval_shape(lambda: {**model.init_kv_cache(num_pages, page_size),
                                           **model.init_state_cache(max_seqs)}))
        out["decode"] = jax.jit(model.decode, donate_argnums=(1,)).lower(
            params, kv, R((max_seqs,), jnp.int32), R((max_seqs,), jnp.int32),
            R((max_seqs, mp), jnp.int32), R((max_seqs,), jnp.bool_),
        ).compile()
        out["prefill_packed"] = jax.jit(model.prefill_packed, donate_argnums=(1,)).lower(
            params, kv, R((lanes, bucket), jnp.int32), R((lanes, bucket), jnp.int32),
            R((lanes, mp), jnp.int32), R((lanes, bucket), jnp.bool_), R((lanes,), jnp.int32),
            R((lanes,), jnp.int32),
        ).compile()
    return out


def compile_window_steps(hf_config: dict, num_pages: int, max_seqs: int, page_size: int = 16,
                         lanes: int = 2, bucket: int = 512, max_model_len: int = 16384,
                         topo=None) -> dict:
    """Compile one decode step and one packed prefill step of a Cohere2-MoE
    model (models/cohere2_moe.py: a page table per attention layer, the window
    kernels, the dropless dispatch over a held share) on one described chip;
    ``hf_config`` is a config.json dict. Returns {step: compiled}."""
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.models.cohere2_moe import Cohere2MoeConfig, Cohere2MoeModel

    one = SingleDeviceSharding((topo or topology()).devices[0])

    def R(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def place(tree):
        return jax.tree.map(lambda x: R(x.shape, x.dtype), tree)

    out = {}
    with on_chip_dispatch():
        model = Cohere2MoeModel(Cohere2MoeConfig.from_hf_config(hf_config))
        mp = model.kv_tables * (max_model_len // page_size)
        params = place(jax.eval_shape(model.init_params, jax.random.key(0)))
        kv = place(jax.eval_shape(lambda: {**model.init_kv_cache(num_pages, page_size),
                                           **model.init_state_cache(max_seqs)}))
        out["decode"] = jax.jit(model.decode, donate_argnums=(1,)).lower(
            params, kv, R((max_seqs,), jnp.int32), R((max_seqs,), jnp.int32),
            R((max_seqs, mp), jnp.int32), R((max_seqs,), jnp.bool_),
        ).compile()
        out["prefill_packed"] = jax.jit(model.prefill_packed, donate_argnums=(1,)).lower(
            params, kv, R((lanes, bucket), jnp.int32), R((lanes, bucket), jnp.int32),
            R((lanes, mp), jnp.int32), R((lanes, bucket), jnp.bool_), R((lanes,), jnp.int32),
        ).compile()
    return out


def _report_steps(title: str, steps: dict) -> None:
    for name, compiled in steps.items():
        m = compiled.memory_analysis()
        text = compiled.as_text()
        print(f"{title} {name}: args {m.argument_size_in_bytes / 2**30:.2f} GiB, "
              f"temp {m.temp_size_in_bytes / 2**30:.2f} GiB, "
              f"output {m.output_size_in_bytes / 2**30:.2f} GiB, "
              f"alias {m.alias_size_in_bytes / 2**30:.2f} GiB per device; "
              f"tpu_custom_call x{text.count('tpu_custom_call')}, "
              f"all-reduce x{text.count('all-reduce(')}", flush=True)


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kernels", action="store_true", help="only the kernel sweep")
    ap.add_argument("--steps", action="store_true", help="only the whole-step compiles")
    ap.add_argument("--window", action="store_true",
                    help="only command-a-plus-ep8: its kernels and whole steps")
    ap.add_argument("--mosaic", metavar="DIR",
                    help="write each tier-1 kernel case's Mosaic module as text and stop")
    ap.add_argument("--tp4-layers", type=int, default=4,
                    help="depth of the Qwen2.5-7B-width model in the tp=4 step")
    args = ap.parse_args(argv)
    topo = topology()
    print(f"compiling for {topo.devices[0].device_kind} x{len(topo.devices)} "
          f"({TOPOLOGY}, described, not attached)", flush=True)
    if args.mosaic:
        Path(args.mosaic).mkdir(parents=True, exist_ok=True)
        cases = kernel_cases(full=False)
        for case in cases:
            (Path(args.mosaic) / f"{case.name}.mlir").write_text(mosaic_text(case, topo))
        print(f"{len(cases)} modules under {args.mosaic}", flush=True)
        return 0
    failed = 0
    bench = Path(__file__).resolve().parents[1] / "benchmark/configs"
    if not args.steps:
        cases = window_cases() if args.window else kernel_cases(full=True)
        for case in cases:
            t0 = time.monotonic()
            try:
                compile_case(case, topo)
                verdict = "ok"
            except Exception as e:  # report every refusal, then fail
                failed += 1
                verdict = "REFUSED " + " ".join(str(e).split())[:240]
            print(f"{case.name}: {verdict} ({time.monotonic() - t0:.1f}s)", flush=True)
    if not args.kernels and not args.window:
        lfm2 = json.loads((bench / "lfm2-8b-a1b-d16.json").read_text())
        slots, pages, max_len = lfm2["benchmark"]["server_args"][1::2]
        _report_steps(f"lfm2-8b-a1b-d16 (16 layers, all 32 experts) {slots} slots, {pages} pages",
                      compile_hybrid_steps(lfm2, num_pages=pages, max_seqs=slots,
                                           max_model_len=max_len, topo=topo))
        falcon = json.loads((bench / "falcon-h1-34b-d6.json").read_text())
        slots, pages, max_len = falcon["benchmark"]["server_args"][1::2]
        _report_steps(f"falcon-h1-34b-d6 (6 layers, whole vocabulary) {slots} slots, {pages} pages",
                      compile_hybrid_steps(falcon, num_pages=pages, max_seqs=slots,
                                           max_model_len=max_len, topo=topo))
    if not args.kernels:
        command_a = json.loads((bench / "command-a-plus-ep8.json").read_text())
        pages = command_a["benchmark"]["server_args"][3]
        _report_steps(f"command-a-plus-ep8 (4 layers, 16 of 128 experts) 48 slots, {pages} pages",
                      compile_window_steps(command_a, num_pages=pages, max_seqs=48, topo=topo))
    if not args.kernels and not args.window:
        _report_steps("tinyllama-1.1b tp=1", compile_steps(TINYLLAMA_GEOMETRY, 1, num_pages=2048, topo=topo))
        qwen = dict(QWEN25_7B_GEOMETRY, num_hidden_layers=args.tp4_layers)
        for tp in (1, 4):
            _report_steps(f"qwen2.5-7b-width L={args.tp4_layers} tp={tp}",
                          compile_steps(qwen, tp, num_pages=512, topo=topo))
        nemotron = json.loads((bench / "nemotron3-super-ep4.json").read_text())
        _report_steps("nemotron3-super-ep4 (11 blocks, 128 of 512 experts) 128 slots",
                      compile_hybrid_steps(nemotron, num_pages=49152, max_seqs=128, topo=topo))
    print(f"{'FAILED' if failed else 'ok'}: {failed} refused", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
