"""Tokenizer wrappers + incremental streaming detokenization.

Mirrors the reference's tokenizer layer (reference: lib/llm/src/tokenizers.rs,
tokenizers/hf.rs, and the DecodeStream used by the backend, backend.rs:111).

Implementations:
  - ``HfTokenizer``: HuggingFace (transformers AutoTokenizer), incl. jinja chat
    templates from tokenizer_config.json
  - ``ByteTokenizer``: hermetic test tokenizer (utf-8 bytes + bos/eos), so the
    full serving path runs with no model files (the reference ships vendored
    tokenizer fixtures for the same reason, lib/llm/tests/data/sample-models/)
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Optional, Protocol, Sequence

_preproc_pool = None
_preproc_lock = threading.Lock()


def preprocessing_executor():
    """Small dedicated pool for CPU-bound request preprocessing (chat-template
    render + BPE encode).

    Why not the default executor: HfTokenizer keeps one underlying tokenizer
    per THREAD (the PyO3 binding is not concurrency-safe — see HfTokenizer),
    so preprocessing on the default asyncio executor loads one duplicate
    ``AutoTokenizer.from_pretrained`` copy per executor thread it ever lands
    on (dozens of threads => dozens of multi-MB tokenizer copies and cold
    ~100ms loads mid-traffic). A 4-worker pool bounds that to 4 loads while
    still covering request-burst parallelism (encode releases the GIL).
    """
    global _preproc_pool
    if _preproc_pool is None:
        with _preproc_lock:
            if _preproc_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                _preproc_pool = ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="dyntpu-preproc"
                )
    return _preproc_pool


class Tokenizer(Protocol):
    vocab_size: int
    eos_token_ids: tuple[int, ...]

    def encode(self, text: str) -> list[int]: ...

    def decode(self, ids: Sequence[int], skip_special_tokens: bool = True) -> str: ...

    def apply_chat_template(
        self,
        messages: list[dict],
        add_generation_prompt: bool = True,
        tools: Optional[list] = None,
    ) -> str: ...


class ByteTokenizer:
    """utf-8 byte-level tokenizer: ids 0..255 bytes, 256 bos, 257 eos."""

    BOS = 256
    EOS = 257

    def __init__(self):
        self.vocab_size = 258
        self.eos_token_ids = (self.EOS,)

    def encode(self, text: str) -> list[int]:
        return list(text.encode("utf-8"))

    def decode(self, ids: Sequence[int], skip_special_tokens: bool = True) -> str:
        data = bytes(i for i in ids if i < 256)
        return data.decode("utf-8", errors="replace")

    def apply_chat_template(
        self,
        messages: list[dict],
        add_generation_prompt: bool = True,
        tools: Optional[list] = None,
    ) -> str:
        parts = [f"<{m['role']}>{m.get('content') or ''}</{m['role']}>" for m in messages]
        if tools:
            import json as _json

            parts.insert(0, f"<tools>{_json.dumps(tools, separators=(',', ':'))}</tools>")
        if add_generation_prompt:
            parts.append("<assistant>")
        return "\n".join(parts)


class HfTokenizer:
    """HF fast tokenizers are NOT safe for concurrent encode/template calls
    (the PyO3 binding raises "Already borrowed" when two threads touch one
    instance — huggingface/tokenizers#537), and the HTTP service runs
    preprocessing on a thread pool. Each thread therefore lazily loads its
    OWN underlying tokenizer (thread-local); vocab/eos metadata comes from
    the construction-time instance and is immutable."""

    def __init__(self, path: str):
        import threading

        self._path = path
        self._local = threading.local()
        tok = self._tok
        self.vocab_size = len(tok)
        eos = tok.eos_token_id
        ids = []
        if eos is not None:
            ids.append(eos)
        # some models define additional end ids in generation config (e.g.
        # llama-3 <|eot_id|>); include any token literally named like an end tag
        self.eos_token_ids = tuple(ids)

    @property
    def _tok(self):
        tok = getattr(self._local, "tok", None)
        if tok is None:
            # a tokenizer needs no framework. Left to look for one,
            # transformers imports torch, sklearn and scipy: 20-27 s between
            # "engine ready" and the first request served (PERF.md, PR 29)
            for framework in ("USE_TORCH", "USE_TF", "USE_FLAX"):
                os.environ.setdefault(framework, "0")
            from transformers import AutoTokenizer

            tok = AutoTokenizer.from_pretrained(self._path)
            self._local.tok = tok
        return tok

    def encode(self, text: str) -> list[int]:
        return self._tok.encode(text, add_special_tokens=False)

    def decode(self, ids: Sequence[int], skip_special_tokens: bool = True) -> str:
        return self._tok.decode(ids, skip_special_tokens=skip_special_tokens)

    def apply_chat_template(
        self,
        messages: list[dict],
        add_generation_prompt: bool = True,
        tools: Optional[list] = None,
    ) -> str:
        # only forward tools when present: older transformers lack the kwarg
        kwargs = {"tools": tools} if tools is not None else {}
        return self._tok.apply_chat_template(
            messages,
            tokenize=False,
            add_generation_prompt=add_generation_prompt,
            **kwargs,
        )


def get_tokenizer(spec: str) -> Tokenizer:
    """'byte' -> ByteTokenizer; anything else -> HF from local path."""
    if spec == "byte":
        return ByteTokenizer()
    if Path(spec).exists():
        return HfTokenizer(spec)
    raise ValueError(f"unknown tokenizer spec {spec!r} (no egress: must be local)")


class DecodeStream:
    """Incremental detokenizer that never emits partial UTF-8/merge artifacts.

    Standard sliding-window scheme: decode(ids[prefix:]) vs decode(ids[prefix:read])
    and emit the suffix once it stabilizes (no trailing replacement char).
    """

    def __init__(self, tokenizer: Tokenizer, prompt_ids: Sequence[int] = (),
                 skip_special_tokens: bool = True):
        self.tokenizer = tokenizer
        self.ids: list[int] = list(prompt_ids)
        self.prefix_offset = len(self.ids)
        self.read_offset = len(self.ids)
        self.skip_special_tokens = skip_special_tokens

    def step(self, token_id: int) -> Optional[str]:
        self.ids.append(token_id)
        return self._emit_stable()

    def step_many(self, token_ids) -> Optional[str]:
        """Append a window of tokens and emit the stabilized text delta in ONE
        pair of decode calls (the per-token loop costs two tokenizer crossings
        per token; windows arrive decode_steps at a time from the engine).

        If the window's tail is mid-codepoint the whole batched delta would be
        withheld, so fall back to per-token stepping for that window — it
        emits everything that stabilizes and holds only the dangling bytes,
        exactly like the per-token path."""
        token_ids = list(token_ids)
        if not token_ids:
            return None
        if len(token_ids) == 1:
            return self.step(token_ids[0])
        mark = len(self.ids)
        self.ids.extend(token_ids)
        new_text = self.tokenizer.decode(
            self.ids[self.prefix_offset :],
            skip_special_tokens=self.skip_special_tokens,
        )
        if not new_text.endswith("�"):
            prefix_text = self.tokenizer.decode(
                self.ids[self.prefix_offset : self.read_offset],
                skip_special_tokens=self.skip_special_tokens,
            )
            if len(new_text) > len(prefix_text):
                delta = new_text[len(prefix_text) :]
                self.prefix_offset = self.read_offset
                self.read_offset = len(self.ids)
                return delta
            return None
        del self.ids[mark:]
        parts = [d for d in (self.step(t) for t in token_ids) if d]
        return "".join(parts) or None

    def _emit_stable(self) -> Optional[str]:
        prefix_text = self.tokenizer.decode(
            self.ids[self.prefix_offset : self.read_offset],
            skip_special_tokens=self.skip_special_tokens,
        )
        new_text = self.tokenizer.decode(
            self.ids[self.prefix_offset :],
            skip_special_tokens=self.skip_special_tokens,
        )
        if new_text.endswith("�"):
            return None  # mid-codepoint; wait for more tokens
        if len(new_text) > len(prefix_text):
            delta = new_text[len(prefix_text) :]
            self.prefix_offset = self.read_offset
            self.read_offset = len(self.ids)
            return delta
        return None
