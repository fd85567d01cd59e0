"""Sampling under jit: greedy / temperature / top-k / top-p, fully batched.

Per-slot sampling parameters are arrays so one compiled function serves any mix
of requests (no recompiles on parameter changes, XLA-friendly static shapes).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

_NEG_INF = -1e30

MAX_EOS_IDS = 8  # per-slot EOS ids carried on device for min_tokens masking


def fold_seed(seed) -> int:
    """Any user seed (64-bit, negative, ...) -> nonzero int31 device seed;
    only ``None`` maps to 0 (= unseeded). One folding used by prefill AND
    decode so a request's stream is consistent across both. ``seed=0`` is a
    real seed (it folds to 1): a user asking for seed 0 gets the same
    deterministic stream every run, not the engine's shared key stream."""
    if seed is None:
        return 0
    return (int(seed) % 0x7FFFFFFE) + 1


@dataclass
class SamplingParams:
    """Per-request sampling options (reference: lib/llm/src/protocols/common.rs
    SamplingOptions/StopConditions)."""

    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0  # 0 => disabled
    top_p: float = 1.0  # 1.0 => disabled
    min_p: float = 0.0  # 0 => disabled; keep tokens with p >= min_p * p_max
    max_tokens: int = 512
    min_tokens: int = 0  # EOS suppressed until this many tokens generated
    stop: Sequence[str] = ()
    seed: Optional[int] = None  # per-request deterministic sampling stream
    ignore_eos: bool = False
    # vLLM-semantics penalties (the reference's engine behavior):
    # presence/frequency over OUTPUT tokens, repetition over prompt + output
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0

    @property
    def needs_penalties(self) -> bool:
        return (
            self.presence_penalty != 0.0
            or self.frequency_penalty != 0.0
            or self.repetition_penalty != 1.0
        )


def apply_penalties(
    logits: jnp.ndarray,  # [B, V] float32
    counts: jnp.ndarray,  # [B, V] int32 output-token counts
    seen: jnp.ndarray,  # [B, V] bool, token in prompt or output
    presence: jnp.ndarray,  # [B]
    frequency: jnp.ndarray,  # [B]
    repetition: jnp.ndarray,  # [B] (1.0 = off)
) -> jnp.ndarray:
    """vLLM-semantics sampling penalties (what the reference's engines do),
    in vLLM's order: repetition divides positive / multiplies negative RAW
    logits of any seen token FIRST, then presence/frequency subtract over
    output-token occurrences."""
    rep = repetition[:, None]
    penalized = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen, penalized, logits)
    cf = counts.astype(jnp.float32)
    logits = logits - frequency[:, None] * cf
    logits = logits - presence[:, None] * (cf > 0)
    return logits


def filter_keep_mask(
    logits: jnp.ndarray,  # [B, V] float32
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 = off)
    top_p: jnp.ndarray,  # [B] (1.0 = off)
    min_p: jnp.ndarray | None = None,  # [B] (0 = off)
) -> jnp.ndarray:
    """[B, V] bool mask of tokens surviving top-k/top-p/min-p, shared by
    sample_tokens and speculative acceptance so both paths draw from the
    identical filtered distribution."""
    B, V = logits.shape
    temp = jnp.where(temperature > 0, temperature, 1.0)[:, None]
    # Sort once (descending); top-k and top-p become rank/cdf thresholds.
    sorted_logits = -jnp.sort(-logits, axis=-1)  # [B, V] descending

    # top-k: keep entries with logit >= k-th largest value
    k = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
    kth_value = jnp.take_along_axis(sorted_logits, (k - 1)[:, None], axis=-1)
    keep_k = logits >= kth_value

    # top-p: over the sorted distribution (temperature-scaled), keep the
    # prefix whose cumulative probability is < p (always keeping the first)
    sorted_probs = jax.nn.softmax(sorted_logits / temp, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    sorted_keep = (cum - sorted_probs) < top_p[:, None]  # prefix incl. first
    num_keep = jnp.maximum(jnp.sum(sorted_keep, axis=-1), 1)
    p_value = jnp.take_along_axis(sorted_logits, (num_keep - 1)[:, None], axis=-1)
    keep_p = logits >= p_value

    keep = keep_k & keep_p
    if min_p is not None:
        # keep tokens whose (tempered) prob >= min_p * max prob: in logit
        # space, logit/temp >= max/temp + log(min_p)
        max_l = jnp.max(logits, axis=-1, keepdims=True)
        thresh = max_l / temp + jnp.log(jnp.maximum(min_p, 1e-10))[:, None]
        keep_m = (logits / temp) >= thresh
        keep = keep & jnp.where(min_p[:, None] > 0, keep_m, True)
    return keep


@jax.named_scope("sample")
def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float32
    key: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 = off)
    top_p: jnp.ndarray,  # [B] (1.0 = off)
    min_p: jnp.ndarray | None = None,  # [B] (0 = off)
    seeds: jnp.ndarray | None = None,  # [B] int32, 0 = unseeded
    positions: jnp.ndarray | None = None,  # [B] sampling-step index per slot
) -> jnp.ndarray:
    """Sample one token per slot. Greedy where temperature <= 0.

    Seeded slots (seeds != 0) draw from a per-request stream keyed by
    (seed, position) — deterministic across retries, preemption, and batch
    composition. Unseeded slots share the engine's key stream."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.where(temperature > 0, temperature, 1.0)[:, None]

    def draw(masked):
        if seeds is None:
            return jax.random.categorical(key, masked / temp).astype(jnp.int32)
        # per-slot keys: seeded slots fold (seed, position) off a fixed base
        # so their stream ignores batch placement; unseeded fold the slot
        # index off the engine's window key
        base = jax.random.key(0x5EED)
        pos = positions if positions is not None else jnp.zeros(B, jnp.int32)

        def slot_key(i, seed, p):
            seeded = jax.random.fold_in(jax.random.fold_in(base, seed), p)
            unseeded = jax.random.fold_in(key, i)
            return jax.lax.cond(seed != 0, lambda: seeded, lambda: unseeded)

        keys = jax.vmap(slot_key)(jnp.arange(B, dtype=jnp.int32), seeds, pos)
        return jax.vmap(
            lambda k_, row: jax.random.categorical(k_, row)
        )(keys, masked / temp).astype(jnp.int32)

    def filtered():
        keep = filter_keep_mask(logits, temperature, top_k, top_p, min_p=min_p)
        return draw(jnp.where(keep, logits, _NEG_INF))

    # Runtime-gated fast paths (lax.cond executes one branch on TPU): the
    # full-vocab sort/cumsum machinery only runs when some slot has an active
    # filter (with none, the keep-mask is all-true, so `draw(logits)` is
    # bit-identical), and RNG runs only when some slot actually samples.
    need_filter = jnp.any((top_k > 0) | (top_p < 1.0))
    if min_p is not None:
        need_filter |= jnp.any(min_p > 0)
    any_sampling = jnp.any(temperature > 0)

    sampled = jax.lax.cond(
        any_sampling,
        lambda: jax.lax.cond(need_filter, filtered, lambda: draw(logits)),
        lambda: greedy,
    )
    return jnp.where(temperature > 0, sampled, greedy)


LOGPROBS_K = 20  # top alternatives computed on device (= the OpenAI API max)


@jax.named_scope("sample")
def sample_tokens_with_logprobs(
    logits: jnp.ndarray,  # [B, V] float32, possibly penalized/masked
    key: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    raw_logits: jnp.ndarray | None = None,  # pre-penalty/mask model logits
    **kwargs,  # min_p / seeds / positions, forwarded to sample_tokens
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """sample_tokens + OpenAI-style logprobs of the model distribution.

    Returns (tokens [B], chosen_logprob [B], topk_ids [B, K], topk_logprobs
    [B, K]). Logprobs are log-softmax of the RAW model logits (pass
    ``raw_logits`` when sampling from penalized/EOS-masked ones) — the
    model's distribution, matching the OpenAI API semantic; sampling itself
    applies temperature/top-k/top-p (and any forwarded filters).
    """
    tokens = sample_tokens(logits, key, temperature, top_k, top_p, **kwargs)
    logprobs = jax.nn.log_softmax(
        logits if raw_logits is None else raw_logits, axis=-1
    )
    chosen = jnp.take_along_axis(logprobs, tokens[:, None].astype(jnp.int32), -1)[:, 0]
    top_vals, top_ids = jax.lax.top_k(logprobs, LOGPROBS_K)
    return tokens, chosen, top_ids.astype(jnp.int32), top_vals


def accept_speculative(
    logits: jnp.ndarray,  # [B, K+1, V] float32; row i predicts position p+i+1
    drafts: jnp.ndarray,  # [B, K] int32 proposed tokens (pad rows arbitrary)
    n_drafts: jnp.ndarray,  # [B] int32 real drafts per slot (<= K)
    key: jax.Array,
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (0 = off)
    top_p: jnp.ndarray,  # [B] (1.0 = off)
    min_p: jnp.ndarray | None = None,  # [B] (0 = off)
    seeds: jnp.ndarray | None = None,  # [B] int32, 0 = unseeded
    positions: jnp.ndarray | None = None,  # [B] anchor fed position per slot
    draft_probs: jnp.ndarray | None = None,  # [B, K, V] real draft dists q
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Speculative acceptance over one verify pass: (tokens [B, K+1], n_emit [B]).

    A slot's verify pass fed [t_p, d_1..d_K] at positions p..p+K, so
    ``logits[:, i]`` is the target distribution for the token at position
    p+i+1 conditioned on a correct prefix through d_i. Per slot the caller
    emits ``tokens[:n_emit]``; drafts beyond the first rejection are dead
    (their KV is overwritten by the next pass at the new anchor).

    Greedy slots (temperature <= 0): a draft is accepted iff it equals the
    raw-logits argmax, so the emitted chain is token-identical to the
    non-speculative engine; ``tokens`` are the argmax rows themselves
    (accepted drafts == their argmax; the row after the last acceptance is
    the correction/bonus token).

    Sampling slots: distribution-exact rejection sampling (Leviathan et al. /
    Chen et al.). Without ``draft_probs`` the proposal is treated as
    degenerate (one-hot q, the n-gram case): accept d_i with probability
    min(1, p(d_i)); on rejection resample from p with d_i removed. With
    ``draft_probs`` (a draft model's real distributions, q[:, i] being the
    filtered distribution d_{i+1} was sampled from) the full rule runs:
    accept d_i with probability min(1, p(d_i)/q(d_i)), and on rejection
    resample from the residual max(0, p - q) renormalized; when every draft
    is accepted, the bonus token samples from the last row unmodified. p is
    the FULL filtered distribution (temperature/top-k/top-p/min-p) via
    filter_keep_mask, so the emitted marginal matches sample_tokens exactly.
    Seeded slots draw from a (seed, position, row) stream — deterministic
    across retries and batch composition, but a distinct stream from the
    non-speculative sampler's (only the distribution is guaranteed equal).
    """
    B, K1, V = logits.shape
    K = K1 - 1
    flat = logits.reshape(B * K1, V)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K1] raw argmax
    draft_valid = jnp.arange(K, dtype=jnp.int32)[None, :] < n_drafts[:, None]

    # greedy acceptance: count of leading argmax matches among real drafts
    g_match = (greedy[:, :K] == drafts) & draft_valid
    g_acc = jnp.cumprod(g_match.astype(jnp.int32), axis=1).sum(axis=1)  # [B]

    # target distribution: identical filtering to sample_tokens, per row
    def per_row(a):  # [B] -> [B*K1] slot params broadcast over rows
        return jnp.repeat(a, K1)

    temps_r = per_row(temperature)
    keep = filter_keep_mask(
        flat, temps_r, per_row(top_k), per_row(top_p),
        min_p=None if min_p is None else per_row(min_p),
    )
    temp_r = jnp.where(temps_r > 0, temps_r, 1.0)[:, None]
    probs = jax.nn.softmax(
        jnp.where(keep, flat, _NEG_INF) / temp_r, axis=-1
    ).reshape(B, K1, V)
    p_draft = jnp.take_along_axis(
        probs[:, :K], drafts[..., None].astype(jnp.int32), axis=-1
    )[..., 0]  # [B, K]

    # per-(slot, row) keys: seeded slots fold (seed, anchor position, row) off
    # a fixed base so their stream ignores batch placement; unseeded fold the
    # slot index off this round's engine key (same scheme as sample_tokens)
    base = jax.random.key(0x5EC5)
    pos = positions if positions is not None else jnp.zeros(B, jnp.int32)
    sd = seeds if seeds is not None else jnp.zeros(B, jnp.int32)

    def slot_key(i, seed, p):
        seeded = jax.random.fold_in(jax.random.fold_in(base, seed), p)
        unseeded = jax.random.fold_in(key, i)
        return jax.lax.cond(seed != 0, lambda: seeded, lambda: unseeded)

    slot_keys = jax.vmap(slot_key)(jnp.arange(B, dtype=jnp.int32), sd, pos)
    rows = jnp.arange(K1, dtype=jnp.int32)
    row_keys = jax.vmap(
        lambda k_: jax.vmap(lambda t: jax.random.fold_in(k_, t))(rows)
    )(slot_keys)  # [B, K1] keys

    # rejection test per draft row (computed in parallel; the cumprod makes
    # acceptance stop at the first rejection, matching the sequential rule)
    u = jax.vmap(jax.vmap(lambda k_: jax.random.uniform(jax.random.fold_in(k_, 0))))(
        row_keys[:, :K]
    )
    if draft_probs is None:
        s_match = (u < p_draft) & draft_valid
    else:
        # real proposal: accept with probability min(1, p(d)/q(d)); q > 0
        # wherever the draft actually sampled, the floor only guards pads
        q_draft = jnp.take_along_axis(
            draft_probs, drafts[..., None].astype(jnp.int32), axis=-1
        )[..., 0]  # [B, K]
        s_match = (u * jnp.maximum(q_draft, 1e-20) < p_draft) & draft_valid
    s_acc = jnp.cumprod(s_match.astype(jnp.int32), axis=1).sum(axis=1)  # [B]

    a = jnp.where(temperature > 0, s_acc, g_acc)  # [B] accepted drafts

    b_idx = jnp.arange(B)
    rejected = a < n_drafts
    final_keys = jax.vmap(lambda k_: jax.random.fold_in(k_, 1))(row_keys[b_idx, a])
    if draft_probs is None:
        # final token: row a's filtered logits; on a rejection the rejected
        # draft is removed — the residual max(0, p - q) for a one-hot q
        row_logits = jnp.where(keep, flat, _NEG_INF).reshape(B, K1, V)[b_idx, a]
        d_rej = jnp.take_along_axis(
            drafts, jnp.clip(a, 0, max(K - 1, 0))[:, None], axis=1
        )[:, 0]
        row_logits = row_logits.at[b_idx, d_rej].add(
            jnp.where(rejected, _NEG_INF, 0.0)
        )
        final = jax.vmap(
            lambda k_, row, t: jax.random.categorical(k_, row / jnp.where(t > 0, t, 1.0))
        )(final_keys, row_logits, temperature).astype(jnp.int32)
    else:
        # final token in probability space (temperature already applied by
        # the softmax above): rejection -> the renormalized residual
        # max(0, p - q) at row a; all-accepted -> the bonus row's p itself.
        # categorical(log p) == categorical(logits/temp) bit for bit (the
        # gumbel draw is shift-invariant), so the q -> one-hot limit matches
        # the branch above exactly.
        p_rows = probs[b_idx, a]  # [B, V]
        q_rows = draft_probs[b_idx, jnp.clip(a, 0, max(K - 1, 0))]
        res = jnp.maximum(p_rows - q_rows, 0.0)
        # a residual can only be empty through float cancellation (p == q
        # rejects with probability 0); fall back to p rather than NaN
        has_res = jnp.sum(res, axis=-1, keepdims=True) > 0
        use_res = rejected[:, None] & has_res
        dist = jnp.where(use_res, res, p_rows)
        final = jax.vmap(
            lambda k_, row: jax.random.categorical(
                k_, jnp.where(row > 0, jnp.log(jnp.maximum(row, 1e-38)), _NEG_INF)
            )
        )(final_keys, dist).astype(jnp.int32)

    drafts_pad = jnp.concatenate(
        [drafts.astype(jnp.int32), jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    out_sampled = jnp.where(
        jnp.arange(K1, dtype=jnp.int32)[None, :] < a[:, None], drafts_pad,
        final[:, None],
    )
    out = jnp.where(temperature[:, None] > 0, out_sampled, greedy)
    return out, a + 1
