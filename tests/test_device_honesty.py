"""No fallback that hides the device (ISSUE 21 item 4) and a compile cache
that can be placed from outside (item 5): the platform is `tpu` or it is not,
the cache directory is the environment's or one fixed path in the checkout,
and what cannot be used is an error."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


# ---------------- _on_tpu ----------------


def test_on_tpu_is_the_platform_name_and_nothing_else(monkeypatch):
    import jax

    from dynamo_tpu.ops import attention

    assert attention._on_tpu() is False  # this test process runs on the CPU
    assert attention.use_pallas_decode(128, 8) is False  # reference by default off the chip
    monkeypatch.setenv("DYNTPU_PALLAS", "1")  # interpret mode, asked for by name
    assert attention.use_pallas_decode(128, 8) is True
    monkeypatch.delenv("DYNTPU_PALLAS")

    # a backend that merely LOOKS like a TPU (the old device-kind guess) is not one
    class Lookalike:
        device_kind = "TPU v5 lite"
        platform = "other"

    monkeypatch.setattr(jax, "default_backend", lambda: "other")
    monkeypatch.setattr(jax, "devices", lambda *a: [Lookalike()])
    assert attention._on_tpu() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._on_tpu() is True

    # and a failure to ask is a failure, not "False"
    def boom():
        raise RuntimeError("backend init failed")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="backend init failed"):
        attention._on_tpu()


def test_reference_path_under_tp_is_logged_once_with_the_reason(monkeypatch):
    """The returns to the gather reference under tensor parallelism used to be
    silent; they now say, once per process and shape, which path and why."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from dynamo_tpu.ops import attention

    monkeypatch.setenv("DYNTPU_PALLAS", "1")
    monkeypatch.setattr(attention, "_logged_paths", set())
    seen = []
    monkeypatch.setattr(attention.log, "info", lambda msg, *a: seen.append(msg % a))
    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    # TinyLlama's 4 kv heads of 64 lanes cannot be split four ways
    q = jnp.zeros((2, 32, 64), jnp.float32)
    pool = jnp.zeros((8, 4, 4 * 64), jnp.float32)
    args = (q, pool, pool, jnp.zeros((2, 2), jnp.int32), jnp.zeros(2, jnp.int32))
    for _ in range(2):
        attention.dispatch_paged_decode_attention(*args, mesh=mesh)
    assert len(seen) == 1, seen
    assert "decode -> reference" in seen[0] and "tp=4 leaves 64 folded lanes per shard" in seen[0]


# ---------------- the compile cache helper ----------------

_PROBE = """
import json, os, sys
import jax
calls = []
real = jax.config.update
jax.config.update = lambda k, v: (calls.append([k, v]), real(k, v))[1]
from dynamo_tpu.utils.xla_cache import cache_stats, enable_compilation_cache
path = enable_compilation_cache()
print(json.dumps({"returned": path, "updates": calls,
                  "jax_dir": jax.config.jax_compilation_cache_dir,
                  "stats_dir": cache_stats()["dir"]}))
"""


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=str(ROOT), env=env,
                         capture_output=True, text=True, check=True).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_cache_dir_from_the_environment_is_not_overridden(tmp_path):
    want = str(tmp_path / "placed-from-outside")
    got = _probe(want)
    assert got["returned"] == want and got["jax_dir"] == want and got["stats_dir"] == want
    assert got["updates"] == []  # JAX honours the variable itself; nothing set in code
    assert os.path.isdir(want)


def test_default_cache_dir_is_one_fixed_path_in_the_checkout():
    from dynamo_tpu.utils.xla_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == ROOT / ".xla_cache"
    first, second = _probe(None), _probe(None)  # two processes, one path
    assert first == second
    assert first["returned"] == first["jax_dir"] == str(ROOT / ".xla_cache")
    assert first["updates"] == [["jax_compilation_cache_dir", str(ROOT / ".xla_cache")]]
    assert ".xla_cache/" in _gitignore()


def test_unusable_cache_dir_is_an_error_at_startup(monkeypatch, tmp_path):
    from dynamo_tpu.utils.xla_cache import enable_compilation_cache

    blocker = tmp_path / "a-file"
    blocker.write_text("not a directory")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "cache"))
    with pytest.raises(RuntimeError, match="is not usable.*JAX_COMPILATION_CACHE_DIR"):
        enable_compilation_cache()


def _gitignore() -> list:
    return (ROOT / ".gitignore").read_text().split()


_NOT_COMMITTED = {".git", ".chip_smoke", ".xla_cache", ".archive_check", ".bench_check",
                  ".cache", "chiprun_out", "__pycache__", "_build"}


def _files(skip=frozenset()):
    """Paths of the files git would commit, as `Path`s under ROOT."""
    for dirpath, dirnames, filenames in os.walk(ROOT):
        dirnames[:] = [d for d in dirnames if d not in _NOT_COMMITTED and d not in skip]
        for f in filenames:
            yield Path(dirpath) / f


def _product_sources():
    """Python files git would commit, tests aside."""
    for path in _files(skip={"tests"}):
        if path.suffix == ".py":
            yield str(path.relative_to(ROOT)), path.read_text()


def test_no_other_site_sets_a_cache_directory():
    """Every entry point that starts an engine goes through the one helper."""
    sources = dict(_product_sources())
    sets_dir = sorted(p for p, text in sources.items()
                      if "jax_compilation_cache_dir" in text or "JAX_COMPILATION_CACHE_DIR" in text)
    # chip_smoke.py only READS the variable, to print where the cache is
    assert sets_dir == ["chip_smoke.py", "dynamo_tpu/utils/xla_cache.py"], sets_dir
    assert "config.update" not in sources["chip_smoke.py"]
    callers = {p for p, text in sources.items() if "enable_compilation_cache()" in text}
    assert callers >= {
        "chip_smoke.py", "dynamo_tpu/launch/_run_impl.py",
        "dynamo_tpu/components/worker.py", "dynamo_tpu/components/prefill_worker.py",
        "dynamo_tpu/sdk/serve_worker.py",
    }, callers


# ---------------- native libraries ----------------


def test_native_libraries_are_built_not_tracked():
    assert list((ROOT / "native").glob("*.so")) == []  # nothing built sits beside the sources
    assert {"native/_build/", "native/*.so"} <= set(_gitignore())
    sys.path.insert(0, str(ROOT / "native"))
    try:
        import build as native_build
    finally:
        sys.path.pop(0)
    try:
        first = native_build.build()
    except (OSError, subprocess.CalledProcessError) as e:
        pytest.skip(f"no native toolchain: {e}")
    # keyed by the sources' content: a second call builds nothing new
    assert native_build.build() == first and first.parent == ROOT / "native" / "_build"
    assert len(first.stem.rsplit("-", 1)[1]) == 16


# ---------------- the kernel choice is not an operator's setting ----------------


def test_ops_and_models_read_no_environment_but_the_platform_override():
    """Which kernel runs follows from platform, dtype, shape and mesh. The one
    variable the ops and models may read is DYNTPU_PALLAS (`pallas_flag`: the
    platform override tier-1 uses to reach interpret mode)."""
    readers = {}
    for sub in ("ops", "models"):
        for path in sorted((ROOT / "dynamo_tpu" / sub).rglob("*.py")):
            lines = [ln.strip() for ln in path.read_text().splitlines()
                     if "os.environ" in ln or "getenv" in ln]
            if lines:
                readers[str(path.relative_to(ROOT))] = lines
    assert readers == {
        "dynamo_tpu/ops/attention.py": ['flag = os.environ.get("DYNTPU_PALLAS")'],
    }, readers


# ---------------- documents name files that exist ----------------

_PATH_IN_DOC = re.compile(r"(?<![\w./*<{-])([\w*][\w./*-]*\.(?:py|sh))\b")


@pytest.mark.parametrize("doc", ["README.md", "ARCHITECTURE.md", "COMPONENTS.md"])
def test_every_script_a_document_names_exists(doc):
    """A `*.py` / `*.sh` path in a document is a promise that the file is
    there: whole from the root, relative to the package, by its own name, or
    as a glob that matches something."""
    import fnmatch

    files = [str(p.relative_to(ROOT)) for p in _files()]
    missing = []
    for name in sorted(set(_PATH_IN_DOC.findall((ROOT / doc).read_text()))):
        pattern = name.lstrip("./")
        if not any(fnmatch.fnmatch(f, pattern) or fnmatch.fnmatch(f, "*/" + pattern) for f in files):
            missing.append(name)
    assert missing == [], f"{doc} names files that are not in the tree: {missing}"
