"""Scenario specs: parametric descriptions of "millions of users" traffic.

A ``ScenarioSpec`` is everything needed to *deterministically* compile a
replayable trace (loadgen/trace.py): an arrival process (Poisson, bursty
on/off Poisson, or a diurnal sinusoid — a scaled day), heavy-tailed ISL/OSL
sampled from parametric distributions (lognormal body, optional Pareto tail),
multi-tenant adapter churn (zipf hot/cold LoRA adapters), long-context
sessions with shared prefixes, and multimodal image requests (Qwen2-VL).

Everything here is pure stdlib — no jax, no numpy — so scenario compilation
and the ``--dry-run`` CLI stay sub-second and importable anywhere (the
determinism contract rides ``random.Random(seed)``, whose generators are
stable across platforms).

Builtin scenarios (``BUILTIN_SCENARIOS``) are the replay CLI's workload
shapes; YAML/dict overrides layer on top via ``load_scenario``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

ARRIVALS = ("poisson", "bursty", "diurnal", "uniform")
LENGTH_DISTS = ("lognormal", "pareto", "fixed")


@dataclass(frozen=True)
class ScenarioSpec:
    """One scenario's complete, seedable description. Frozen: a spec is a
    value — compile_trace(spec) is a pure function of it."""

    name: str
    seed: int = 0
    # ---------------- arrival process ----------------
    num_requests: int = 64
    arrival: str = "poisson"  # poisson | bursty | diurnal | uniform
    rate_rps: float = 8.0  # mean arrival rate over the trace
    # bursty: on/off modulated Poisson — rate multiplies by burst_factor for
    # burst_duty of every burst_period_s (thinning keeps the MEAN at rate_rps)
    burst_factor: float = 4.0
    burst_period_s: float = 4.0
    burst_duty: float = 0.25
    # diurnal: sinusoidal rate over diurnal_period_s (a scaled "day");
    # amplitude 1.0 swings between 0 and 2x the mean
    diurnal_period_s: float = 60.0
    diurnal_amplitude: float = 0.8
    # ---------------- prompt/output lengths (heavy-tailed) ----------------
    isl_dist: str = "lognormal"
    isl_mean: int = 64  # body median, tokens
    isl_sigma: float = 0.6  # lognormal sigma (spread)
    isl_min: int = 4
    isl_max: int = 512
    osl_dist: str = "lognormal"
    osl_mean: int = 16
    osl_sigma: float = 0.5
    osl_min: int = 2
    osl_max: int = 256
    # pareto tail exponent (isl/osl_dist == "pareto"); smaller = heavier
    tail_alpha: float = 2.5
    # ---------------- multi-tenant / adapters ----------------
    tenants: tuple = ()  # e.g. ("tenant-a", "tenant-b"); uniform draw
    adapters: tuple = ()  # LoRA adapter names; zipf hot/cold draw
    zipf_alpha: float = 1.2  # adapter popularity skew (1 = mild, 2 = extreme)
    base_model_share: float = 0.0  # fraction of requests on the base model
    # ---------------- sessions / shared prefixes ----------------
    # >0: requests belong to session groups; each group shares a common
    # prefix of shared_prefix_len tokens (system prompt / document context —
    # the prefix-cache + long-context shape)
    session_groups: int = 0
    shared_prefix_len: int = 0
    # ---------------- multi-turn parked sessions ----------------
    # session_turns > 1: each of num_requests arrivals starts a CONVERSATION
    # of that many turns; turn k's prompt extends turn k-1's prompt with a
    # fresh tail (the conversation history), and consecutive turns are
    # spaced park_s seconds apart — the session goes COLD between turns, so
    # its KV blocks demote down the tier ladder (HBM -> host -> disk,
    # engine/kv_store.py) and the next turn's TTFT measures the resume path
    session_turns: int = 1
    park_s: float = 0.0
    # ---------------- multimodal ----------------
    images: bool = False  # attach one deterministic random image per request
    image_hw: tuple = (32, 32)
    # ---------------- token space ----------------
    vocab: int = 512  # prompt token ids drawn from [1, vocab)
    temperature: float = 0.0
    # ---------------- SLO budgets (the goodput verdict) ----------------
    slo_ttft_ms: Optional[float] = 2000.0
    slo_itl_ms: Optional[float] = 200.0  # budget on each request's ITL p99

    def __post_init__(self):
        if self.arrival not in ARRIVALS:
            raise ValueError(f"arrival must be one of {ARRIVALS}; got {self.arrival!r}")
        for d in (self.isl_dist, self.osl_dist):
            if d not in LENGTH_DISTS:
                raise ValueError(f"length dist must be one of {LENGTH_DISTS}; got {d!r}")
        if self.num_requests < 1:
            raise ValueError("num_requests must be >= 1")
        if self.rate_rps <= 0:
            raise ValueError("rate_rps must be > 0")
        if self.session_groups and self.shared_prefix_len <= 0:
            raise ValueError("session_groups needs shared_prefix_len > 0")
        if self.session_turns < 1:
            raise ValueError("session_turns must be >= 1")
        if self.park_s < 0:
            raise ValueError("park_s must be >= 0")
        # yaml lists arrive as lists; freeze to tuples so the spec hashes
        object.__setattr__(self, "tenants", tuple(self.tenants))
        object.__setattr__(self, "adapters", tuple(self.adapters))
        object.__setattr__(self, "image_hw", tuple(self.image_hw))

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def replace(self, **over) -> "ScenarioSpec":
        return dataclasses.replace(self, **over)


def _spec(**kw) -> ScenarioSpec:
    return ScenarioSpec(**kw)


#: The replay CLI's scenario set. Names are stable report keys; geometry
#: scales via replace() at the call site.
BUILTIN_SCENARIOS: dict = {
    # bursty chat: on/off Poisson bursts, heavy-tailed short prompts — the
    # shape that blows ITL p99 when admission serializes prefill ahead of
    # running decodes
    "bursty_chat": _spec(
        name="bursty_chat", arrival="bursty", rate_rps=16.0, burst_factor=4.0,
        num_requests=64, isl_mean=48, isl_max=256, osl_mean=16, osl_max=64,
    ),
    # diurnal: slow sinusoidal load swing (a scaled day) — the planner's
    # scale-up/down signal shape
    "diurnal_chat": _spec(
        name="diurnal_chat", arrival="diurnal", rate_rps=8.0,
        diurnal_period_s=30.0, num_requests=64,
        isl_mean=48, isl_max=256, osl_mean=16, osl_max=64,
    ),
    # multi-tenant LoRA churn: zipf hot/cold adapters over several tenants —
    # exercises slot LRU eviction/hot-swap and the per-tenant SLO series
    "lora_churn": _spec(
        name="lora_churn", arrival="poisson", rate_rps=12.0, num_requests=48,
        tenants=("tenant-a", "tenant-b", "tenant-c"),
        adapters=("a1", "a2", "a3", "a4", "a5", "a6"),
        zipf_alpha=1.3, base_model_share=0.2,
        isl_mean=32, isl_max=128, osl_mean=12, osl_max=48,
    ),
    # long-context sessions: groups sharing a long prefix (system prompt /
    # document) with individual tails — prefix cache, table ladder, offload
    "long_context_sessions": _spec(
        name="long_context_sessions", arrival="poisson", rate_rps=4.0,
        num_requests=24, session_groups=4, shared_prefix_len=192,
        isl_mean=64, isl_sigma=0.4, isl_min=16, isl_max=256,
        osl_mean=16, osl_max=48, slo_ttft_ms=5000.0,
    ),
    # the 128K deep end (standing PR 8/11 follow-up): few, enormous prompts
    # with a shared document prefix — the page-table ladder's widest rung,
    # depth-aware chunking, and pressure-driven host offload all under the
    # SAME goodput verdict as every other scenario. Sized for the serving
    # ladder's 131072 max_model_len (isl_max leaves OSL headroom); CPU smoke
    # replays it scaled down (tests/test_loadgen.py), the driver's TPU run
    # prices it at full depth.
    "long_context_128k": _spec(
        name="long_context_128k", arrival="poisson", rate_rps=0.5,
        num_requests=6, session_groups=2, shared_prefix_len=65536,
        # isl is the per-request TAIL past the shared 64K prefix: total
        # prompt tops out at 65536 + 65024 + OSL < 131072
        isl_dist="lognormal", isl_mean=32768, isl_sigma=0.3,
        isl_min=4096, isl_max=65024,
        osl_dist="fixed", osl_mean=32, osl_max=64,
        vocab=32000, slo_ttft_ms=120000.0, slo_itl_ms=2000.0,
    ),
    # parked sessions: multi-turn conversations that go cold between turns —
    # each arrival is a conversation whose turn k prompt is turn k-1's
    # prompt plus a fresh tail, with park_s of silence in between. While
    # parked, the session's KV blocks demote HBM -> host -> disk; the
    # follow-up turn's TTFT is the cold-resume headline
    "parked_sessions": _spec(
        name="parked_sessions", arrival="poisson", rate_rps=2.0,
        num_requests=8, session_turns=3, park_s=20.0,
        isl_mean=48, isl_sigma=0.4, isl_min=16, isl_max=128,
        osl_dist="fixed", osl_mean=8, osl_max=16, slo_ttft_ms=8000.0,
    ),
    # multimodal: Qwen2-VL image requests (deterministic random images) —
    # the capability that had zero perf numbers before this harness
    "mm_vl": _spec(
        name="mm_vl", arrival="poisson", rate_rps=4.0, num_requests=16,
        images=True, image_hw=(16, 16), isl_dist="fixed", isl_mean=12,
        isl_max=64, osl_dist="fixed", osl_mean=8, osl_max=16,
        slo_ttft_ms=5000.0,
    ),
}


def load_scenario(name_or_spec, **overrides) -> ScenarioSpec:
    """Resolve a scenario: a builtin name, a dict (e.g. one YAML stanza),
    or a ScenarioSpec — with keyword overrides layered on top."""
    if isinstance(name_or_spec, ScenarioSpec):
        spec = name_or_spec
    elif isinstance(name_or_spec, dict):
        spec = ScenarioSpec(**name_or_spec)
    elif name_or_spec in BUILTIN_SCENARIOS:
        spec = BUILTIN_SCENARIOS[name_or_spec]
    else:
        raise ValueError(
            f"unknown scenario {name_or_spec!r} "
            f"(builtins: {sorted(BUILTIN_SCENARIOS)})"
        )
    return spec.replace(**overrides) if overrides else spec


def load_scenarios_yaml(path) -> list[ScenarioSpec]:
    """Scenario list from a YAML file: either ``scenarios: [{...}, ...]``
    stanzas (each a ScenarioSpec dict, ``scenario:`` naming a builtin base)
    or a bare list."""
    import yaml

    with open(path) as f:
        doc = yaml.safe_load(f)
    stanzas = doc.get("scenarios", doc) if isinstance(doc, dict) else doc
    if not isinstance(stanzas, list):
        raise ValueError(f"{path}: expected a scenario list")
    specs = []
    for stanza in stanzas:
        if isinstance(stanza, str):
            specs.append(load_scenario(stanza))
            continue
        stanza = dict(stanza)
        base = stanza.pop("scenario", None)
        if base is not None:
            specs.append(load_scenario(base, **stanza))
        else:
            specs.append(ScenarioSpec(**stanza))
    return specs
