"""What the tests of the three models with a per-slot state share
(tests/test_nemotron_h.py, tests/test_lfm2_moe.py, tests/test_falcon_h1.py):
the benchmark's modules by file, seeded prompts, the model's own step functions
over hand-made caches, one request through an engine."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import EngineRequest

ROOT = Path(__file__).resolve().parents[1]


def bench_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module (the benchmark is no package)."""
    path = ROOT / "benchmark" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tokens(seed: int, n: int, vocab_size: int = 256) -> list:
    return [int(t) for t in np.random.default_rng(seed).integers(3, vocab_size, n)]


def window_off_by_one(conv):
    """The window one position late: every entry moved back by one, the newest
    input lost (what a hand-off that stops one token early leaves)."""
    return jnp.roll(conv, 1, axis=1).at[:, 0].set(0)


class Driver:
    """The model's own prefill and decode functions over hand-made caches:
    what the runner's jitted steps call, without the scheduler."""

    def __init__(self, model, params, max_seqs=3, num_pages=32, page_size=16):
        self.model, self.params = model, params
        self.ps, self.max_seqs = page_size, max_seqs
        self.cache = {**model.init_kv_cache(num_pages, page_size),
                      **model.init_state_cache(max_seqs)}
        self.tables = np.zeros((max_seqs, 8), np.int32)
        for s in range(max_seqs):  # pages 1.. (0 is the null page), 8 a slot
            self.tables[s] = 1 + s * 8 + np.arange(8)

    def prefill(self, lanes, T):
        """lanes: [(slot, tokens, start)]; one packed call at bucket T.
        Returns logits [len(lanes), V] at each lane's last real token."""
        N = len(lanes)
        toks, pos = np.zeros((N, T), np.int32), np.zeros((N, T), np.int32)
        valid, last = np.zeros((N, T), bool), np.zeros(N, np.int32)
        slots, pts = np.zeros(N, np.int32), np.zeros((N, 8), np.int32)
        for j, (slot, tokens, start) in enumerate(lanes):
            n = len(tokens)
            toks[j, :n] = tokens
            pos[j] = start + np.arange(T)
            valid[j, :n] = True
            last[j] = max(0, n - 1)
            slots[j] = slot
            if slot >= 0:
                pts[j] = self.tables[slot]
        logits, self.cache = jax.jit(self.model.prefill_packed)(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(pts),
            jnp.asarray(valid), jnp.asarray(last), state_slots=jnp.asarray(slots),
        )
        return np.asarray(logits)

    def decode(self, fed: dict):
        """fed: {slot: (token, position)}; the other slots are not active."""
        B = self.max_seqs
        toks, pos, act = np.zeros(B, np.int32), np.zeros(B, np.int32), np.zeros(B, bool)
        for slot, (t, p) in fed.items():
            toks[slot], pos[slot], act[slot] = t, p, True
        logits, self.cache = jax.jit(self.model.decode)(
            self.params, self.cache, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(self.tables), jnp.asarray(act),
        )
        return np.asarray(logits)


async def generate(eng, rid, prompt, max_tokens):
    """One greedy request -> (tokens, the logprob of each)."""
    toks, lps = [], []
    req = EngineRequest(request_id=rid, token_ids=list(prompt), logprobs=1,
                        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens))
    async for out in eng.generate(req):
        if out.token is not None:
            toks.append(out.token)
            lps.append(out.logprob)
    return toks, lps
