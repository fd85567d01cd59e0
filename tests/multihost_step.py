"""Child script for test_multihost: one process of a 2-process jax.distributed
CPU mesh. Joins via init_multihost (DYNTPU_COORDINATOR / NUM_PROCESSES /
PROCESS_ID — the same env the helm worker template sets), builds a global
dp=2 x tp=4 mesh spanning both processes, places the tiny Llama model's
params/KV with place_global, and runs one sharded decode step under jit.
Prints CHECKSUM <value>; the parent asserts both processes print the same.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

flags = os.environ.get("XLA_FLAGS", "")
flags = " ".join(f for f in flags.split() if "host_platform_device_count" not in f)
os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from dynamo_tpu.models.llama import LlamaConfig, LlamaModel  # noqa: E402
from dynamo_tpu.parallel.mesh import (  # noqa: E402
    MeshConfig,
    build_mesh,
    init_multihost,
    place_global,
)


def main() -> None:
    init_multihost()
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, jax.devices()

    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=16,
    )
    model = LlamaModel(cfg)
    # same seed in both processes -> identical host values; place_global
    # contributes each process's addressable shards
    params = place_global(model.init_params(jax.random.key(0)), model.param_shardings(mesh))
    kv = place_global(model.init_kv_cache(8, 4), model.kv_cache_sharding(mesh))

    rep = NamedSharding(mesh, P())
    B = 2
    tokens = np.array([5, 9], np.int32)
    positions = np.array([3, 1], np.int32)
    page_tables = np.array([[1, 2, 0, 0], [3, 0, 0, 0]], np.int32)
    active = np.array([True, True])

    step = jax.jit(
        model.decode,
        in_shardings=(
            model.param_shardings(mesh),
            model.kv_cache_sharding(mesh),
            rep, rep, rep, rep,
        ),
        out_shardings=(rep, model.kv_cache_sharding(mesh)),
    )
    logits, kv = step(params, kv, tokens, positions, page_tables, active)
    jax.block_until_ready(logits)
    assert logits.shape == (B, cfg.vocab_size)
    # fully replicated: every process can read its local copy
    local = np.asarray(logits.addressable_shards[0].data, np.float32)
    print(f"CHECKSUM {float(np.sum(local)):.6f} ARGMAX {np.argmax(local, -1).tolist()}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
