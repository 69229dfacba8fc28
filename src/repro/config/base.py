"""Config dataclasses.

``ModelConfig`` is the single declarative description a model is built from;
every assigned architecture is a ``ModelConfig`` instance in
``repro.configs.<id>``. ``InputShape`` describes the four assigned workload
shapes. ``ServingConfig`` parameterises the BCEdge serving layer.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    # identity
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    source: str = ""  # citation from the assignment table

    # trunk dims
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: Optional[int] = None  # default d_model // n_heads
    d_ff: int = 1024
    vocab_size: int = 1024

    # attention flavour
    rope: str = "rope"  # rope | rope2d | mrope | none
    qk_norm: bool = False
    qkv_bias: bool = False  # bias on the q/k/v projections (chatglm3)
    sliding_window: Optional[int] = None  # tokens; None = full attention
    rope_theta: float = 10_000.0

    # per-layer block pattern, cycled over layers. entries:
    #   "attn" (global; MoE FFN when n_experts > 0), "attn_dense" (global
    #   attention with a dense FFN even in MoE models — llama4 interleave),
    #   "local_attn" (windowed), "rglru" (RG-LRU), "rwkv"
    block_pattern: Tuple[str, ...] = ("attn",)

    # MoE
    n_experts: int = 0  # 0 = dense FFN
    top_k: int = 1
    moe_dense_residual: bool = False  # arctic: dense MLP in parallel w/ MoE
    dense_ff: Optional[int] = None  # width of the dense residual MLP
    capacity_factor: float = 1.25

    # encoder-decoder
    enc_dec: bool = False
    n_enc_layers: int = 0

    # modality frontend stub: None | "vision" | "audio"
    frontend: Optional[str] = None
    frontend_tokens: int = 0  # stub embeddings prepended at prefill

    # misc
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-6  # every norm of the trunk, q/k norms included
    activation: str = "silu"  # silu | gelu
    tie_embeddings: bool = False
    rwkv_head_size: int = 64
    rglru_width: Optional[int] = None  # RG-LRU recurrent width (default d_model)
    logit_softcap: Optional[float] = None

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // max(self.n_heads, 1))
        if self.family in ("vlm", "audio") and self.frontend is None:
            object.__setattr__(
                self, "frontend", "vision" if self.family == "vlm" else "audio"
            )
        assert self.n_heads % max(self.n_kv_heads, 1) == 0, (
            f"{self.name}: n_heads={self.n_heads} not divisible by "
            f"n_kv_heads={self.n_kv_heads}"
        )

    # ---- derived ------------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    def layer_kinds(self) -> Tuple[str, ...]:
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))

    @property
    def attention_free(self) -> bool:
        return all(k in ("rwkv", "rglru") for k in self.layer_kinds())

    @property
    def subquadratic(self) -> bool:
        """True if no layer attends over unbounded context."""
        for k in self.layer_kinds():
            if k in ("attn", "attn_dense") and self.sliding_window is None:
                return False
        return True

    def param_count_estimate(self, active_only: bool = False) -> int:
        """Analytic parameter count (embeddings + trunk), used for rooflines.

        ``active_only`` counts only the routed experts a token actually
        uses (top_k of n_experts) — the MoE "active params" figure.
        """
        d, hd = self.d_model, self.head_dim
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        attn_p = (d * hd * self.n_heads + 2 * d * hd * self.n_kv_heads
                  + hd * self.n_heads * d)
        if self.qkv_bias:
            attn_p += hd * (self.n_heads + 2 * self.n_kv_heads)
        gated = self.activation in ("silu", "geglu")
        n_mats = 3 if gated else 2

        def dense_ffn(width):
            return n_mats * d * width

        moe_ffn = 0
        if self.n_experts:
            n_e = self.top_k if active_only else self.n_experts
            moe_ffn = n_e * 3 * d * self.d_ff + d * self.n_experts
            if self.moe_dense_residual:
                moe_ffn += 3 * d * (self.dense_ff or self.d_ff)

        total = emb
        rec_w = self.rglru_width or d
        for k in self.layer_kinds():
            if k in ("attn", "local_attn"):
                total += attn_p
                total += moe_ffn if self.n_experts else dense_ffn(self.d_ff)
            elif k == "attn_dense":
                total += attn_p + dense_ffn(self.dense_ff or self.d_ff)
            elif k == "rglru":
                total += (2 * d * rec_w + 2 * rec_w * rec_w + 4 * rec_w
                          + rec_w * d) + dense_ffn(self.d_ff)
            elif k == "rwkv":
                total += (6 * d * d + 10 * d * 32          # time mix
                          + 2 * d * self.d_ff + d * d)     # channel mix
        if self.enc_dec:
            total += self.n_enc_layers * (attn_p + dense_ffn(self.d_ff))
            total += self.n_layers * attn_p  # decoder cross-attention
        return int(total)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """BCEdge scheduler + serving layer parameters (paper §IV/§V-A).

    ``exec_mode`` selects the execution substrate the scheduler drives
    (docs/ARCHITECTURE.md §5):

    * ``"round"`` — the paper's semantics: a (b, m_c) round runs to
      completion, every request in the batch waits for the slowest one.
    * ``"continuous"`` — iteration-level batching: the action is
      reinterpreted as (max slots per instance, concurrency); requests
      join/leave the running batch at decode-iteration boundaries.

    ``decode_steps_mean`` parameterises how autoregressive the workload
    is: each request needs a geometrically-distributed number of decode
    iterations with this mean (1.0 = the paper's single-shot CNN/BERT
    requests, where both modes coincide round-for-round).

    ``prefill_tokens_mean`` > 0 gives continuous-mode requests a prompt
    that must be prefilled before decoding (geometric, that mean);
    ``token_budgets`` then becomes a third co-optimised action axis —
    the per-iteration cap on prefill-chunk + decode tokens (0 =
    uncapped), the knob that bounds iteration latency under long-prompt
    arrivals (docs/ARCHITECTURE.md §5). ``preemption`` enables the
    SLO-aware eviction policy (trigger/victim/hysteresis in
    docs/RUNTIME.md §8) in the continuous simulator.

    ``shared_prefix_tokens`` > 0 makes the workload a *templated* one
    (docs/ARCHITECTURE.md §5): each request's prompt starts with one of
    ``prefix_population`` shared prefixes of that length (system prompts
    / per-model task preambles), on top of its geometric unique tail.
    With ``prefix_cache`` on, the simulator's sessions skip the prefill
    of a prefix an earlier request of the same model already paid — the
    analytic twin of the engine's block-sharing prefix cache, so learned
    policies see cache dynamics.
    """

    batch_sizes: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64, 128)
    concurrency_levels: Tuple[int, ...] = (1, 2, 3, 4, 5, 6, 7, 8)
    arrival_rps: float = 30.0  # Poisson rate (paper: 30 rps)
    platform: str = "xavier_nx"  # see serving/platforms.py
    slo_scale: float = 1.0  # multiply per-model SLOs (stress knob)
    max_queue: int = 512
    seed: int = 0
    use_interference_predictor: bool = True
    exec_mode: str = "round"  # "round" | "continuous"
    decode_steps_mean: float = 1.0  # mean decode iterations per request
    #: per-iteration token-budget action axis (0 = uncapped); the default
    #: single level keeps the (b, m_c) action space unchanged
    token_budgets: Tuple[int, ...] = (0,)
    prefill_tokens_mean: float = 0.0  # mean prompt tokens (0 = single-shot)
    preemption: bool = False  # SLO-aware eviction (continuous mode)
    preempt_margin_ms: float = 50.0  # victim must out-slack urgent by this
    max_preemptions: int = 2  # per-request cap (anti-thrash)
    #: shared-prefix (templated) workload: prefix length in tokens
    #: (0 = no shared prefixes) drawn from a population of distinct
    #: prefixes; prefix_cache lets sessions skip already-paid prefixes
    shared_prefix_tokens: float = 0.0
    prefix_population: int = 4
    prefix_cache: bool = False
    #: speculative-decoding depth action axis (docs/ARCHITECTURE.md §5):
    #: per-iteration draft depth k (0 = plain autoregressive decode); the
    #: default single level keeps the (b, m_c, tb) action space unchanged
    spec_depths: Tuple[int, ...] = (0,)
    #: simulator twin: probability each draft token is accepted (the
    #: per-draft Bernoulli of the acceptance-dependent step cost model)
    spec_accept_rate: float = 0.6
    #: tensor-parallel degree action axis (docs/RUNTIME.md §10): devices
    #: one instance spans on its 1D ("model",) mesh. OUTERMOST axis, so
    #: the default single level keeps every narrower codec — and every
    #: policy trained before the axis existed — encoding-stable
    tp_degrees: Tuple[int, ...] = (1,)

    def __post_init__(self):
        assert self.exec_mode in ("round", "continuous"), self.exec_mode
        assert self.decode_steps_mean >= 1.0, self.decode_steps_mean
        assert self.token_budgets, "need at least one token-budget level"
        assert all(t >= 0 for t in self.token_budgets), self.token_budgets
        assert self.prefill_tokens_mean >= 0.0, self.prefill_tokens_mean
        assert self.shared_prefix_tokens >= 0.0, self.shared_prefix_tokens
        assert self.prefix_population >= 1, self.prefix_population
        assert self.spec_depths, "need at least one speculation depth"
        assert all(k >= 0 for k in self.spec_depths), self.spec_depths
        assert 0.0 <= self.spec_accept_rate <= 1.0, self.spec_accept_rate
        assert self.tp_degrees, "need at least one TP degree"
        assert all(d >= 1 for d in self.tp_degrees), self.tp_degrees

    @property
    def n_actions(self) -> int:
        return len(self.batch_sizes) * len(self.concurrency_levels) * \
            len(self.token_budgets) * len(self.spec_depths) * \
            len(self.tp_degrees)

    def action_to_pair(self, a: int) -> Tuple[int, int]:
        nb = len(self.batch_sizes)
        a = a % (nb * len(self.concurrency_levels))
        return self.batch_sizes[a % nb], self.concurrency_levels[a // nb]

    def pair_to_action(self, b: int, m_c: int) -> int:
        """(b, m_c) at the first (most permissive) token-budget level —
        the exact pre-token-budget action encoding, kept stable so
        existing callers and trained policies are unaffected."""
        return self.concurrency_levels.index(m_c) * len(self.batch_sizes) + \
            self.batch_sizes.index(b)

    def action_to_triple(self, a: int) -> Tuple[int, int, int]:
        """(b, m_c, token_budget) — token budget 0 means uncapped. The
        modulus folds away any outer (speculation-depth) axis, keeping
        the narrower codec stable for pre-k callers."""
        nb, nm = len(self.batch_sizes), len(self.concurrency_levels)
        nt = len(self.token_budgets)
        a = a % (nb * nm * nt)
        b, m_c = self.action_to_pair(a)
        return b, m_c, self.token_budgets[a // (nb * nm)]

    def triple_to_action(self, b: int, m_c: int, token_budget: int) -> int:
        nb, nm = len(self.batch_sizes), len(self.concurrency_levels)
        return self.token_budgets.index(token_budget) * nb * nm + \
            self.pair_to_action(b, m_c)

    def action_to_quad(self, a: int) -> Tuple[int, int, int, int]:
        """(b, m_c, token_budget, spec_k) — the speculation depth sits
        outside the pair/triple digits: every narrower codec reads the
        same inner digits, so trained policies and existing callers see
        identical encodings at spec_depths=(0,). The modulus folds away
        the (outermost) TP-degree axis for pre-tp callers."""
        nb, nm = len(self.batch_sizes), len(self.concurrency_levels)
        nt, nk = len(self.token_budgets), len(self.spec_depths)
        a = a % (nb * nm * nt * nk)
        b, m_c, tb = self.action_to_triple(a)
        return b, m_c, tb, self.spec_depths[a // (nb * nm * nt)]

    def quad_to_action(self, b: int, m_c: int, token_budget: int,
                       spec_k: int) -> int:
        nb, nm = len(self.batch_sizes), len(self.concurrency_levels)
        nt = len(self.token_budgets)
        return self.spec_depths.index(spec_k) * nb * nm * nt + \
            self.triple_to_action(b, m_c, token_budget)

    def action_to_quint(self, a: int) -> Tuple[int, int, int, int, int]:
        """(b, m_c, token_budget, spec_k, tp_degree) — the TP degree is
        the OUTERMOST axis (same construction as the spec_k axis before
        it), so at tp_degrees=(1,) every action encodes exactly as the
        quad codec and narrower callers fold it away by modulus."""
        nb, nm = len(self.batch_sizes), len(self.concurrency_levels)
        nt, nk = len(self.token_budgets), len(self.spec_depths)
        b, m_c, tb, sk = self.action_to_quad(a)
        return b, m_c, tb, sk, self.tp_degrees[a // (nb * nm * nt * nk)]

    def quint_to_action(self, b: int, m_c: int, token_budget: int,
                        spec_k: int, tp_degree: int) -> int:
        nb, nm = len(self.batch_sizes), len(self.concurrency_levels)
        nt, nk = len(self.token_budgets), len(self.spec_depths)
        return self.tp_degrees.index(tp_degree) * nb * nm * nt * nk + \
            self.quad_to_action(b, m_c, token_budget, spec_k)
