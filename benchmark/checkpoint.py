"""Seeded HF-format checkpoint for a benchmark configuration: the writer,
the tokenizer and the table of normal quantiles. WHICH tensors a checkpoint
holds is not decided here: a configuration names its tensor plan
(`benchmark.checkpoint`, a file in `checkpoints/`; `dense` where it names
none), and `ensure_checkpoint` writes what that plan lists.

Copied from `tools/make_hf_checkpoint.py` (config.json + model.safetensors +
tokenizer files, random-normal weights at 0.02), so that a later PR may change
the program's tool and not the yardstick. Corrected:

- tied embeddings are written once (`tie_word_embeddings: true` in the config
  and no second head in the file, see `checkpoints/dense.py`): the original
  always wrote an untied head, which is a different model from the published
  one and 0.6 GB more;
- tensors are made in parallel, one numpy stream per tensor keyed by (seed,
  tensor index), directly in bfloat16 (16-bit draws looked up in a table of
  normal quantiles) and written straight to their offsets in the safetensors
  file: the original made 3 G float32 normals on one core and held them all
  (80 s for 6 GB at PR 21's 77 MB/s; set-up is paid in every run);
- the tokenizer covers every id of the vocabulary (a word-level table, id n
  decodes to "t<n>"). The original trains a BPE on a small synthetic corpus
  and reaches a fraction of a 152k vocabulary; ids beyond it decode to "", the
  server sends no SSE chunk for an empty text, and a client then sees its
  first token late or never. Published tokenizers decode every id.

The checkpoint is a pure function of (geometry, plan, seed).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import statistics
import struct
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

WEIGHT_SCALE = 0.02
SPECIAL_TOKENS = ["<s>", "</s>", "<unk>"]  # ids 0, 1, 2

_CHUNK = 1 << 18  # elements per draw: the index block stays in the core's cache


def _normal_table() -> np.ndarray:
    """The 65536 quantiles of N(0, WEIGHT_SCALE), rounded to bfloat16 (to
    nearest even), as their bit patterns. A uniform 16-bit draw looked up here
    is a normal draw in bfloat16, at a tenth of the cost of drawing in float32
    and converting."""
    dist = statistics.NormalDist(0.0, WEIGHT_SCALE)
    q = np.array([dist.inv_cdf((i + 0.5) / 65536) for i in range(65536)], np.float32)
    u = q.view(np.uint32)
    return ((u + 0x7FFF + ((u >> 16) & 1)) >> 16).astype(np.uint16)


_ONE_BF16 = 0x3F80  # 1.0


def tensor_values(seed: int, index: int, shape: tuple, kind: str, table: np.ndarray) -> np.ndarray:
    """The bfloat16 bit patterns (uint16) of tensor `index`, flat."""
    n = int(np.prod(shape))
    if kind == "ones":
        return np.full(n, _ONE_BF16, np.uint16)
    rng = np.random.Generator(np.random.SFC64(np.random.SeedSequence([seed, index])))
    out = np.empty(n, np.uint16)
    for lo in range(0, n, _CHUNK):
        hi = min(n, lo + _CHUNK)
        np.take(table, rng.integers(0, 65536, hi - lo, dtype=np.uint16), out=out[lo:hi])
    return out


def write_safetensors(path: Path, plan: list, seed: int, workers: int) -> int:
    """`plan` is [(name, shape, kind)] in file order, kind "normal" or "ones":
    what a `checkpoints/<name>.py` `tensor_plan(cfg)` returns."""
    header, offset = {}, 0
    for name, shape, _ in plan:
        nbytes = int(np.prod(shape)) * 2
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [offset, offset + nbytes]}
        offset += nbytes
    head = json.dumps(header, separators=(",", ":")).encode()
    head += b" " * (-len(head) % 8)
    base = 8 + len(head)
    table = _normal_table()
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        os.pwrite(fd, struct.pack("<Q", len(head)) + head, 0)
        os.ftruncate(fd, base + offset)

        def one(i: int) -> None:
            name, shape, kind = plan[i]
            view = memoryview(tensor_values(seed, i, shape, kind, table).view(np.uint8))
            at, done = base + header[name]["data_offsets"][0], 0
            while done < len(view):
                done += os.pwrite(fd, view[done:done + (1 << 28)], at + done)

        # largest first, so that the embedding does not trail alone at the end
        order = sorted(range(len(plan)), key=lambda i: -int(np.prod(plan[i][1])))
        with ThreadPoolExecutor(workers) as pool:
            for f in [pool.submit(one, i) for i in order]:
                f.result()
    finally:
        os.close(fd)
    return base + offset


def write_tokenizer(out: Path, vocab_size: int) -> None:
    """A word-level table over the whole vocabulary: id n <-> "t<n>"."""
    vocab = {tok: i for i, tok in enumerate(SPECIAL_TOKENS)}
    for i in range(len(SPECIAL_TOKENS), vocab_size):
        vocab[f"t{i}"] = i
    added = [{"id": i, "content": tok, "single_word": False, "lstrip": False,
              "rstrip": False, "normalized": False, "special": True}
             for i, tok in enumerate(SPECIAL_TOKENS)]
    (out / "tokenizer.json").write_text(json.dumps({
        "version": "1.0", "truncation": None, "padding": None,
        "added_tokens": added, "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"}, "post_processor": None,
        "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab, "unk_token": "<unk>"},
    }, separators=(",", ":")))
    (out / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": "<s>", "eos_token": "</s>", "unk_token": "<unk>",
        "clean_up_tokenization_spaces": False,
        "model_max_length": 1 << 20,
    }, indent=1))
    (out / "special_tokens_map.json").write_text(json.dumps({
        "bos_token": "<s>", "eos_token": "</s>", "unk_token": "<unk>"}))


def token_id_of(text: str) -> int:
    """Inverse of the tokenizer's decode for one token ("t123" -> 123)."""
    text = text.strip()
    if text in SPECIAL_TOKENS:
        return SPECIAL_TOKENS.index(text)
    return int(text[1:])


def ensure_checkpoint(cache: Path, name: str, hf_config: dict, seed: int, plan_module,
                      workers: int | None = None) -> tuple:
    """(directory, made-now, seconds, bytes). `plan_module` is the
    configuration's `checkpoints/<benchmark.checkpoint>.py`: its
    `tensor_plan(config)` says which tensors the file holds. One checkpoint
    per configuration is kept: a set of runs uses a new seed each time, and a
    full-size one is 6 GB."""
    t0 = time.monotonic()
    out = cache / f"ckpt-{name}-seed{seed}"
    stamp = out / ".complete"
    config = {"hidden_act": "silu", "bos_token_id": 0, "eos_token_id": 1,
              "torch_dtype": "bfloat16", **hf_config}
    plan = [(n, tuple(int(d) for d in shape), kind) for n, shape, kind in plan_module.tensor_plan(config)]
    want = json.dumps({"config": hf_config, "seed": seed, "format": 3,
                       "plan": hashlib.sha256(json.dumps(plan).encode()).hexdigest()}, sort_keys=True)
    if stamp.exists() and stamp.read_text() == want:
        size = (out / "model.safetensors").stat().st_size
        return out, False, time.monotonic() - t0, size
    for old in cache.glob(f"ckpt-{name}-seed*"):
        shutil.rmtree(old, ignore_errors=True)
    out.mkdir(parents=True)
    (out / "config.json").write_text(json.dumps(config, indent=1))
    write_tokenizer(out, config["vocab_size"])
    size = write_safetensors(out / "model.safetensors", plan, seed,
                             workers or min(12, os.cpu_count() or 4))
    stamp.write_text(want)
    return out, True, time.monotonic() - t0, size
