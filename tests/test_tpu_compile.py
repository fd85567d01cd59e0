"""The serving kernels compile for one TPU v5e chip — asked of the chip's own
compiler, with no chip attached (tools/tpu_compile.py; on-chip-measurement
guide section 2, rehearsal 3). Interpret mode on the CPU, which every other
kernel test uses, can show none of what this refuses: scoped-VMEM overruns,
DMA slices not aligned to the tiling, head counts Mosaic cannot tile.

One case per kernel and published head geometry; the shapes the seed's kernels
were refused at are among them. A compile that passes is not a chip run:
chip_smoke.py's kernel-parity phase is."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from tools import tpu_compile  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    try:
        return tpu_compile.topology()
    except Exception as e:  # no libtpu, or it cannot describe a v5e here
        pytest.skip(f"TPU topology {tpu_compile.TOPOLOGY} cannot be described: {e}")


CASES = tpu_compile.kernel_cases(full=False)


@pytest.mark.parametrize("case", CASES, ids=[c.name for c in CASES])
def test_kernel_compiles_for_v5e(topo, case):
    compiled = tpu_compile.compile_case(case, topo)
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the program"


def test_one_kv_head_per_shard_folds_the_pool(topo):
    """Qwen2.5-7B's 4 kv heads at tp=4 leave one head per chip, which Mosaic
    cannot DMA-slice from a [ps, Hkv, D] page: the engine folds the pool
    instead (LlamaModel.kv_folded) and the shard_map'd folded kernels compile
    for the described 2x2 mesh, with the tensor-parallel all-reduces."""
    steps = tpu_compile.compile_steps(
        dict(tpu_compile.QWEN25_7B_GEOMETRY, num_hidden_layers=1), tp=4, num_pages=64,
        max_seqs=4, lanes=1, bucket=128, topo=topo,
    )
    for name, compiled in steps.items():
        text = compiled.as_text()
        assert "tpu_custom_call" in text, f"{name}: attention fell back to the reference"
        assert "all-reduce(" in text, f"{name}: no tensor-parallel collective"
        assert compiled.memory_analysis().argument_size_in_bytes > 0
