"""Multi-model concurrent serving runtime: the REAL m_c axis
(docs/RUNTIME.md; state machine, admission rules and Eq.-1 accounting
are specified there).

BCEdge's scheduler co-optimises batch size and the number of concurrent
model instances, but until this module the second axis only existed
analytically in the simulator. ``ModelInstancePool`` owns N live
``ContinuousBatchingEngine`` instances across heterogeneous
``ModelConfig``s, so a ``(b, m_c)`` action really creates/destroys
concurrent engine instances:

* **router** — one earliest-deadline-first queue per model; at every
  iteration boundary waiting requests are admitted into the least-loaded
  RUNNING instance of their model (docs/RUNTIME.md admission rules);
* **lifecycle** — ``scale_to(model, m_c)`` spawns or drains instances
  (STARTING → RUNNING → DRAINING → RETIRED); draining instances finish
  their resident sequences before they are retired, so scale-down never
  truncates in-flight work;
* **interference path** — every ``step()`` measures the wall-clock
  iteration latency together with the number of live instances that
  overlapped it; the samples calibrate the contention model
  (``latency_model.fit_contention``) and, via ``engine_features``, feed
  the §IV-F NN interference predictor with real measurements.

Instances of the same model share weights and jit caches
(``ContinuousBatchingEngine(share_from=...)``) so ``spawn`` is cheap
enough to be a per-decision action; each instance keeps its own KV
block pool, which is what actually bounds m_c on a real host.

The pool can share ONE ``kv_block_budget`` across instances
(docs/RUNTIME.md §7): ``spawn``/``scale_to`` are constrained by actual
free blocks, the router's admission gate is the per-engine
``BlockAllocator``, and every pure-decode iteration records real
occupancy samples that calibrate ``latency_model.fit_occupancy`` — the
measured memory model the ``PoolScheduler``'s Eq.-4 guard checks
proposed (b, m_c) actions against, in place of the analytic
``instance_memory_gb`` curve.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.config.base import ModelConfig
from repro.core.interference import engine_features
from repro.core.utility import utility
from repro.serving import latency_model as lm
from repro.serving import request as lifecycle
from repro.serving.engine import (ContinuousBatchingEngine,
                                  ContinuousResult, PreemptedRequest,
                                  supports_prefix_cache,
                                  supports_speculation, to_recompute)
from repro.serving.request import RequestLifecycle
from repro.serving.tracing import span

# instance lifecycle states (docs/RUNTIME.md state machine)
STARTING = "starting"
RUNNING = "running"
DRAINING = "draining"
RETIRED = "retired"

_seq = itertools.count()

#: trailing window the contention/occupancy fits read (and the bound the
#: sample lists are trimmed to, so long-lived serving loops do not leak)
_SAMPLE_WINDOW = 512

#: engine counters ``stats()`` sums over the live instances
ENGINE_COUNTERS = ("n_decode_rows", "n_prefill_chunk_tokens",
                   "n_prefill_pad_rows", "n_compiled_steps",
                   "n_decode_flat_steps", "n_decode_kv_blocks")


@dataclasses.dataclass
class PoolRequest:
    """One request routed by the pool (paper §III-A-1, with an absolute
    deadline for the EDF router)."""
    request_id: int
    model: str
    prompt: np.ndarray
    slo_ms: float
    max_new_tokens: int
    submit_s: float            # pool clock
    admit_s: float = -1.0      # set by the router at admission
    #: preempted-sequence snapshot awaiting re-admission; the router
    #: resumes it via ``engine.submit_resume`` instead of a fresh submit
    #: (docs/RUNTIME.md §8)
    resume: Optional[PreemptedRequest] = None
    n_preempted: int = 0
    #: pool-clock time the first token landed (-1 before any token);
    #: pool-level so it survives cross-instance preemption/resume, where
    #: engine clocks are not comparable
    first_token_s: float = -1.0
    #: tokens streamed to listeners so far (highest global index + 1)
    n_streamed: int = 0
    #: push-mode state machine + callbacks (docs/RUNTIME.md §11); always
    #: attached by ``submit`` — front-ends hook it via ``add_listener``
    lifecycle: Optional[RequestLifecycle] = None

    @property
    def deadline_s(self) -> float:
        return self.submit_s + self.slo_ms / 1000.0


@dataclasses.dataclass
class PoolResult:
    """One finished (or rejected) request, with per-request Eq.-3
    utility computed at completion time."""
    request_id: int
    model: str
    instance_id: int           # -1 when rejected before admission
    tokens: np.ndarray
    submit_s: float
    admit_s: float
    finish_s: float
    slo_ms: float
    utility: float = 0.0
    rejected: bool = False
    #: torn down before finishing (client disconnect / explicit cancel);
    #: ``tokens`` holds the partial completion
    cancelled: bool = False
    #: pool-clock first-token time (-1 if no token landed)
    first_token_s: float = -1.0

    @property
    def latency_ms(self) -> float:
        return (self.finish_s - self.submit_s) * 1000.0

    @property
    def ttft_ms(self) -> float:
        """Submit -> first token (-1 if no token landed)."""
        return (self.first_token_s - self.submit_s) * 1000.0 \
            if self.first_token_s >= 0 else -1.0

    @property
    def tpot_ms(self) -> float:
        """Mean ms per token after the first (-1 below two tokens)."""
        if self.first_token_s < 0 or len(self.tokens) < 2:
            return -1.0
        return (self.finish_s - self.first_token_s) * 1000.0 \
            / (len(self.tokens) - 1)

    @property
    def violated(self) -> bool:
        # a cancelled request has no completion to be late — the client
        # walked away; report() counts cancellations separately
        if self.cancelled:
            return False
        return self.rejected or self.latency_ms > self.slo_ms


def _mesh_devices(mesh) -> tuple:
    """Devices a mesh spans; empty for an unplaced engine (None: it runs
    on the default device). An instance's device set, and with the
    model name the key of its weight/jit template."""
    return tuple(mesh.devices.flat) if mesh is not None else ()


class ModelInstance:
    """One live engine instance plus its lifecycle state and the pool's
    per-instance bookkeeping (resident requests, Eq.-1 slot share)."""

    def __init__(self, instance_id: int, model: str,
                 engine: ContinuousBatchingEngine, kv_blocks: int = 0,
                 tp_degree: int = 1):
        self.instance_id = instance_id
        self.model = model
        self.engine = engine
        self.kv_blocks = kv_blocks  # share of the pool's block budget
        self.tp_degree = tp_degree  # devices this instance spans
        self.state = STARTING
        self.requests: Dict[int, PoolRequest] = {}  # engine rid -> request
        self.n_served = 0

    @property
    def n_resident(self) -> int:
        """Sequences currently owned by this instance (decoding or
        waiting inside the engine for the next iteration boundary)."""
        return len(self.requests)

    @property
    def free_capacity(self) -> int:
        return self.engine.n_slots - self.n_resident

    @property
    def devices(self) -> tuple:
        """Devices the engine's mesh spans (``_mesh_devices``)."""
        return _mesh_devices(self.engine.mesh if self.engine is not None
                             else None)

    @property
    def slo_sum_ms(self) -> float:
        """Σ SLO over resident requests — this instance's contribution to
        the model's Eq.-1 scheduling slot (docs/RUNTIME.md)."""
        return sum(r.slo_ms for r in self.requests.values())

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"ModelInstance({self.instance_id}, {self.model!r}, "
                f"{self.state}, resident={self.n_resident})")


class ModelInstancePool:
    """N concurrent engine instances behind per-model EDF queues
    (docs/RUNTIME.md). The unit of progress is ``step()``: route waiting
    requests, run one decode iteration on every busy instance, retire
    empty draining instances, and record the iteration's wall latency
    against the overlap level for interference calibration.

    Every engine keeps its KV in a block pool. ``kv_layout`` accepts
    only ``"paged"``: it is kept so that existing callers which name
    the layout stay valid, and selects nothing."""

    def __init__(self, configs: Dict[str, ModelConfig],
                 max_instances: int = 8, max_slots: int = 4,
                 max_seq: int = 128, seed: int = 0,
                 strict_admission: bool = False,
                 predictor=None, kv_layout: str = "paged",
                 block_size: int = 16,
                 kv_block_budget: Optional[int] = None,
                 blocks_per_instance: Optional[int] = None,
                 preemption: bool = False,
                 preempt_margin_ms: float = 50.0,
                 preempt_cooldown_steps: int = 8,
                 max_preemptions: int = 2,
                 kv_host_blocks: int = 0,
                 preempt_mode: str = "auto",
                 token_budget: Optional[int] = None,
                 prefix_cache: bool = False,
                 spec_k: int = 0,
                 tp_degree: int = 1,
                 n_devices: Optional[int] = None):
        self.configs = dict(configs)
        self.max_instances = max_instances
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.seed = seed
        self.strict_admission = strict_admission
        self.predictor = predictor
        if kv_layout != "paged":
            raise ValueError(
                f"kv_layout {kv_layout!r}: the block pool ('paged') is "
                "the only KV layout")
        #: KV budget (docs/RUNTIME.md §7): with ``kv_block_budget`` set
        #: the pool shares ONE block budget across all instances — memory
        #: becomes a managed resource instead of the analytic
        #: latency_model curve
        self.block_size = block_size
        self.kv_block_budget = kv_block_budget
        self.kv_blocks_free = kv_block_budget
        #: vLLM-style prefix caching (docs/ARCHITECTURE.md §5): engines
        #: share full immutable prompt blocks at refcount+1 and
        #: the router gains prefix affinity. Models whose layer stack
        #: cannot page every decode state (recurrent/windowed/frontend)
        #: silently serve without it — per-model capability, one flag.
        self.prefix_cache = prefix_cache
        #: speculative decoding (docs/ARCHITECTURE.md §speculation): the
        #: construction-time CAP on the proposal depth — engines are
        #: built with scratch capacity for ``spec_cap`` draft tokens and
        #: the scheduler's fourth axis (``set_spec_k``) moves the LIVE
        #: depth anywhere in [0, spec_cap] without respawning. Models
        #: whose cache cannot rewind (recurrent/windowed) silently serve
        #: with k=0, mirroring the prefix-cache capability gate.
        self.spec_cap = max(0, spec_k)
        self.spec_ks: Dict[str, int] = {m: self.spec_cap for m in configs}
        #: tensor parallelism (docs/RUNTIME.md §10): per-model TP degree
        #: — the scheduler's fifth axis (``set_tp_degree``). An instance
        #: at degree d spans d devices of the shared device set on a 1D
        #: ``("model",)`` mesh (heads sharded, block tables replicated),
        #: so ``m_c`` and the degree jointly partition the hardware:
        #: Σ tp over live instances is capped by ``n_devices`` when set.
        #: Unlike spec_k a live engine cannot re-shard, so a degree
        #: change drains mismatched instances and respawns via scale_to.
        self.tp_degrees: Dict[str, int] = {
            m: max(1, tp_degree) for m in configs}
        #: with ``n_devices`` set, the shared device set is the first
        #: ``n_devices`` of ``jax.devices()`` and every spawn is carved
        #: from its FREE devices (``_carve_mesh``), so co-resident
        #: instances sit on disjoint devices, tp=1 included
        self.n_devices = n_devices
        #: target grant for an instance; default = one full-length row
        #: per slot (``_full_grant``). Sizing it from measured occupancy
        #: (``occupancy_tokens_per_seq``) is how the pool fits more
        #: instances into the same budget than full-length rows allow.
        self.blocks_per_instance = blocks_per_instance
        #: SLO-aware preemption policy (docs/RUNTIME.md §8): when the
        #: most urgent waiting request cannot be admitted anywhere and
        #: its slack no longer covers its predicted service time, evict
        #: the largest-slack resident (never a mid-chunk prefill), with
        #: margin + cooldown + per-request-cap hysteresis against thrash
        self.preemption = preemption
        self.preempt_margin_ms = preempt_margin_ms
        self.preempt_cooldown_steps = preempt_cooldown_steps
        self.max_preemptions = max_preemptions
        self.n_preempted = 0
        self.preempts_by_model: Dict[str, int] = {m: 0 for m in configs}
        self._last_preempt_step: Dict[str, int] = {}
        #: host KV tier (docs/RUNTIME.md §8): per-instance host-memory
        #: block pool preempted sequences swap into instead of being
        #: recomputed. ``preempt_mode`` picks the eviction flavour:
        #: "recompute" (legacy), "swap" (always swap when the slot can),
        #: or "auto" — price both with the calibrated token-cost and
        #: swap-bandwidth fits and take the cheaper (``swap_cost``).
        if preempt_mode not in ("recompute", "swap", "auto"):
            raise ValueError(
                f"preempt_mode must be 'recompute', 'swap' or 'auto', "
                f"got {preempt_mode!r}")
        if kv_host_blocks < 0:
            raise ValueError(
                f"kv_host_blocks must be >= 0, got {kv_host_blocks}")
        self.kv_host_blocks = kv_host_blocks
        self.preempt_mode = preempt_mode
        self.n_swap_preempted = 0
        #: per-model per-iteration token budget applied to every live
        #: engine (None = uncapped); the scheduler's third knob
        self.token_budgets: Dict[str, Optional[int]] = {
            m: token_budget for m in configs}
        #: (tokens processed, iteration wall ms) over non-compiling busy
        #: iterations — calibrates latency_model.fit_token_cost
        self.token_samples: List[Tuple[int, float]] = []
        #: the same samples keyed by TP degree, recorded only on
        #: iterations whose busy instances all share one degree — the
        #: per-degree token-cost fits the guard prices layouts with
        #: (mixed-degree iterations feed only the global fit)
        self.tp_token_samples: Dict[int, List[Tuple[int, float]]] = {}
        #: (total resident sequences, Σ kv_used_tokens) per pure-decode
        #: iteration — calibrates latency_model.fit_occupancy
        self.occupancy_samples: List[Tuple[int, int]] = []
        self.instances: Dict[str, List[ModelInstance]] = {
            m: [] for m in self.configs}
        self.slot_caps: Dict[str, int] = {m: max_slots for m in self.configs}
        self.queues: Dict[str, List[tuple]] = {m: [] for m in self.configs}
        #: weight/jit donors keyed (model, device ids) — instances share
        #: a template only on the same devices (engine.share_from needs
        #: an equal mesh: the params live there)
        self._templates: Dict[Tuple[str, tuple],
                              ContinuousBatchingEngine] = {}
        self.admission_log: List[Tuple[int, int]] = []  # (request, instance)
        self.retired: List[ModelInstance] = []
        self.n_rejected = 0
        self.n_cancelled = 0
        self.n_steps = 0
        #: per-request event listeners (docs/RUNTIME.md §11): request_id
        #: -> callable taking one dict per event ("prefill", "decode",
        #: "token", "preempted", "finished", "cancelled", "rejected").
        #: Fired synchronously inside pool calls — a front-end bridges to
        #: its own loop (e.g. asyncio call_soon_threadsafe). A listener
        #: that raises is dropped: one dead client must not take the
        #: serving loop down.
        self._listeners: Dict[int, Callable] = {}
        self.n_listener_errors = 0
        #: client-observed serving metrics, HTTP-independent (pool clock,
        #: submit -> first token / finish): ms samples for the stats()
        #: percentiles, trimmed to the trailing window like every other
        #: sample list
        self.ttft_samples: List[float] = []
        self.tpot_samples: List[float] = []
        #: (total live instances, iteration wall ms) calibration samples
        self.contention_samples: List[Tuple[int, float]] = []
        self._results: Dict[str, List[PoolResult]] = {
            m: [] for m in self.configs}
        self._next_rid = 0
        self._next_iid = 0
        self._t0 = time.perf_counter()

    # ---- clock -----------------------------------------------------------
    def now(self) -> float:
        return time.perf_counter() - self._t0

    # ---- push-mode events (docs/RUNTIME.md §11) --------------------------
    def add_listener(self, request_id: int, fn: Callable) -> None:
        """Register ``fn(event_dict)`` for every lifecycle event of
        ``request_id``. One listener per request; removed automatically
        on the terminal event (or when it raises)."""
        self._listeners[request_id] = fn

    def remove_listener(self, request_id: int) -> None:
        self._listeners.pop(request_id, None)

    def _emit(self, req: PoolRequest, event: str, **payload) -> None:
        fn = self._listeners.get(req.request_id)
        if fn is None:
            return
        ev = {"event": event, "request_id": req.request_id,
              "t_s": self.now()}
        ev.update(payload)
        try:
            fn(ev)
        except Exception:  # noqa: BLE001 — dead client, not our bug
            self.n_listener_errors += 1
            self._listeners.pop(req.request_id, None)

    def _on_engine_token(self, inst: "ModelInstance", erid: int,
                         tok: int, idx: int) -> None:
        """Engine emitted one token for the sequence it knows as
        ``erid``: stamp pool-clock first-token time and push the event.
        ``idx`` is the global completion index (stable across
        preemption), so ``n_streamed`` never double-counts a resume."""
        req = inst.requests.get(erid)
        if req is None:  # defensive: engine-local sequence (warm drain)
            return
        now = self.now()
        if req.first_token_s < 0:
            req.first_token_s = now
        req.n_streamed = max(req.n_streamed, idx + 1)
        if req.lifecycle is not None:
            req.lifecycle.token(int(tok), int(idx), now)
        self._emit(req, "token", token=int(tok), index=int(idx))

    def _on_engine_state(self, inst: "ModelInstance", erid: int,
                         state: str) -> None:
        """Engine moved the sequence between phases ("prefill" at slot
        assignment, "decode" at prefill completion) — advance the
        lifecycle machine and surface the event."""
        req = inst.requests.get(erid)
        if req is None:
            return
        if req.lifecycle is not None and not req.lifecycle.terminal:
            req.lifecycle.to(state, self.now())
        self._emit(req, state, instance_id=inst.instance_id)

    # ---- lifecycle (docs/RUNTIME.md state machine) -----------------------
    def live(self, model: Optional[str] = None) -> List[ModelInstance]:
        """RUNNING + DRAINING instances (they still hold resources)."""
        models = [model] if model else list(self.instances)
        return [i for m in models for i in self.instances[m]
                if i.state in (RUNNING, DRAINING)]

    def running(self, model: str) -> List[ModelInstance]:
        return [i for i in self.instances[model] if i.state == RUNNING]

    def m_c(self, model: str) -> int:
        return len(self.running(model))

    def total_live(self) -> int:
        return len(self.live())

    def busy_count(self) -> int:
        """Live instances with resident work — the overlap level the
        contention samples are recorded against (idle instances cost no
        iteration time, so predictions must not count them)."""
        return sum(1 for i in self.live() if i.n_resident > 0)

    # ---- tensor parallelism (docs/RUNTIME.md §10) ------------------------
    def devices_in_use(self) -> int:
        """Devices the live instances span: Σ tp_degree. With
        ``n_devices`` set this is what bounds further spawns — m_c and
        TP degree jointly partition the shared device set."""
        return sum(i.tp_degree for i in self.live())

    def _carve_mesh(self, tp: int):
        """The 1D ``("model",)`` mesh a new instance at degree ``tp``
        spans, carved from the devices no live instance holds — of the
        first ``n_devices`` of ``jax.devices()``, or of all of them when
        ``n_devices`` is None — so placed instances never share a
        device. A tp=1 instance gets a one-device mesh when
        ``n_devices`` is set, and stays unplaced otherwise (None: it
        runs on the default device and holds no device of the set)."""
        if tp <= 1 and self.n_devices is None:
            return None
        import jax
        from repro.launch.mesh import make_tp_mesh
        devices = jax.devices()
        if self.n_devices is not None:
            devices = devices[:self.n_devices]
        held = {d for i in self.live() for d in i.devices}
        free = [d for d in devices if d not in held]
        if len(free) < tp:
            raise RuntimeError(
                f"tp_degree={tp} needs {tp} free devices, have "
                f"{len(free)} of {len(devices)}")
        return make_tp_mesh(tp, free[:tp])

    def set_tp_degree(self, model: str, tp: int) -> None:
        """The fifth knob (docs/RUNTIME.md §10): TP degree for future
        spawns of ``model``. A live engine cannot re-shard its mesh in
        place, so RUNNING instances at a different degree start
        DRAINING (resident work completes first) and the next
        ``scale_to`` respawns at the new degree."""
        tp = max(1, tp)
        if self.tp_degrees.get(model) == tp:
            return
        self.tp_degrees[model] = tp
        for inst in self.instances[model]:
            if inst.state == RUNNING and inst.tp_degree != tp:
                inst.state = DRAINING

    def _full_grant(self) -> int:
        """Worst-case grant: one full ``max_seq`` row of blocks per slot,
        so block availability never refuses what a free slot could
        hold (the engine's own ``kv_blocks`` default)."""
        return self.max_slots * (-(-self.max_seq // self.block_size))

    def _min_viable_blocks(self) -> int:
        """Smallest grant a spawned instance can serve with: one
        slot's worst case, or the operator's explicit (right-sized)
        ``blocks_per_instance`` target if that is smaller — deliberate
        oversubscription against measured occupancy."""
        one_slot = -(-self.max_seq // self.block_size)
        if self.blocks_per_instance:
            return min(one_slot, self.blocks_per_instance)
        return one_slot

    def _spawn_grant(self) -> int:
        """Blocks the next spawn would charge against the budget."""
        return self.blocks_per_instance or self._full_grant()

    def can_spawn(self, model: Optional[str] = None) -> bool:
        """Instance budget, device budget AND block budget allow one
        more spawn — ``scale_to`` is constrained by actual free blocks,
        not the analytic memory curve. An instance can start on a
        partial grant (min one slot). ``model`` prices that model's TP
        degree against the shared device set (degree 1 assumed when
        omitted)."""
        if self.total_live() >= self.max_instances:
            return False
        tp = self.tp_degrees.get(model, 1) if model else 1
        if self.n_devices is not None and \
                self.devices_in_use() + tp > self.n_devices:
            return False
        if self.kv_blocks_free is None:
            return True
        return self.kv_blocks_free >= -(-self._min_viable_blocks() // tp)

    def spawn(self, model: str) -> ModelInstance:
        """STARTING → RUNNING. Raises when the pool-wide instance
        budget, the shared device set or the shared KV block budget is
        exhausted (use scale_to for clamped semantics)."""
        if self.total_live() >= self.max_instances:
            raise RuntimeError(
                f"pool at max_instances={self.max_instances}")
        tp = self.tp_degrees.get(model, 1)
        if self.n_devices is not None and \
                self.devices_in_use() + tp > self.n_devices:
            raise RuntimeError(
                f"device budget exhausted: {model!r} at tp_degree={tp} "
                f"needs {tp} of {self.n_devices} devices, "
                f"{self.n_devices - self.devices_in_use()} free")
        grant = self._spawn_grant()
        charge = grant
        if self.kv_blocks_free is not None:
            # head-sharding spreads every block over the instance's tp
            # devices, so one budget (per-device) block buys tp pool
            # blocks: the charge is ceil(grant / tp) and the engine keeps
            # the full grant (docs/RUNTIME.md §10)
            charge = min(-(-grant // tp), self.kv_blocks_free)
            grant = charge * tp
            if grant < self._min_viable_blocks():
                raise RuntimeError(
                    f"KV block budget exhausted "
                    f"({self.kv_blocks_free} free of "
                    f"{self.kv_block_budget})")
            self.kv_blocks_free -= charge
        kw = {"block_size": self.block_size, "kv_blocks": grant,
              "prefix_cache": self.prefix_cache
              and supports_prefix_cache(self.configs[model]),
              # host tier is single-device: sharded instances keep
              # recompute-on-resume (the engine rejects the combo)
              "kv_host_blocks": self.kv_host_blocks if tp == 1 else 0}
        if self.spec_cap > 0 and supports_speculation(self.configs[model]):
            kw["spec_k"] = self.spec_cap
        mesh = self._carve_mesh(tp)
        key = (model, _mesh_devices(mesh))
        tmpl = self._templates.get(key)
        eng = ContinuousBatchingEngine(
            self.configs[model], max_slots=self.max_slots,
            max_seq=self.max_seq, seed=self.seed, share_from=tmpl,
            token_budget=self.token_budgets.get(model),
            mesh=mesh, **kw)
        # spawn into the CURRENT scheduler-set depth (≤ the built cap)
        eng.spec_k = min(self.spec_ks.get(model, 0), eng.spec_max)
        if tmpl is None:
            self._templates[key] = eng
        inst = ModelInstance(self._next_iid, model, eng, kv_blocks=charge,
                             tp_degree=tp)
        # push-mode hooks (docs/RUNTIME.md §11): the engine reports
        # per-token emissions and phase changes; the pool translates
        # engine request ids to pool requests and fans out to listeners
        eng.on_token = (lambda erid, tok, idx, _inst=inst:
                        self._on_engine_token(_inst, erid, tok, idx))
        eng.on_state = (lambda erid, state, _inst=inst:
                        self._on_engine_state(_inst, erid, state))
        self._next_iid += 1
        self.instances[model].append(inst)
        inst.state = RUNNING  # engine construction == warm start
        return inst

    def drain(self, model: str, instance_id: Optional[int] = None) -> None:
        """RUNNING → DRAINING: no new admissions; resident sequences run
        to completion, then the sweep retires the instance."""
        for inst in self.instances[model]:
            if inst.state == RUNNING and (instance_id is None
                                          or inst.instance_id == instance_id):
                inst.state = DRAINING
                if instance_id is not None:
                    return

    def scale_to(self, model: str, m_c: int) -> int:
        """Set the RUNNING instance count for ``model`` (idempotent).

        Scaling up revives DRAINING instances first (cheapest — their
        engine is already warm), then spawns, clamped to the pool-wide
        ``max_instances`` budget shared by all models. Scaling down
        drains the least-loaded instances. Returns the RUNNING count
        actually reached.
        """
        m_c = max(0, m_c)
        run = self.running(model)
        if len(run) > m_c:
            for inst in sorted(run, key=lambda i: i.n_resident)[
                    : len(run) - m_c]:
                inst.state = DRAINING
            return m_c
        # revive only degree-matched instances: reviving a stale-degree
        # engine would undo a set_tp_degree decision
        draining = [i for i in self.instances[model]
                    if i.state == DRAINING
                    and i.tp_degree == self.tp_degrees.get(model, 1)]
        while len(self.running(model)) < m_c and draining:
            draining.pop(0).state = RUNNING  # revive
        while len(self.running(model)) < m_c and self.can_spawn(model):
            self.spawn(model)
        return len(self.running(model))

    def set_slot_cap(self, model: str, b: int) -> None:
        """The b axis on a live engine: cap concurrently-active slots per
        instance at ``min(b, max_slots)`` (engine slot count is fixed at
        construction; the router enforces the cap at admission)."""
        self.slot_caps[model] = max(1, min(b, self.max_slots))

    def set_token_budget(self, model: str, budget: Optional[int]) -> None:
        """The third knob (docs/RUNTIME.md §8): per-iteration cap on
        prefill-chunk + decode tokens, applied to every live engine of
        ``model`` and inherited by future spawns. ``None`` (or 0) lifts
        the cap."""
        budget = budget or None
        self.token_budgets[model] = budget
        for inst in self.instances[model]:
            if inst.engine is not None:
                inst.engine.token_budget = budget

    def set_spec_k(self, model: str, k: int) -> None:
        """The fourth knob (docs/RUNTIME.md §9): per-iteration speculative
        proposal depth, applied to every live engine of ``model`` and
        inherited by future spawns. Clamped per-engine to the scratch
        capacity the engine was built with (``spec_max``) — an engine
        built without speculation clamps to 0, so the call is always
        safe regardless of model capability."""
        k = max(0, k)
        self.spec_ks[model] = k
        for inst in self.instances[model]:
            if inst.engine is not None:
                inst.engine.spec_k = min(k, inst.engine.spec_max)

    def prefill_backlog_tokens(self, model: Optional[str] = None) -> int:
        """Prompt tokens queued or mid-chunk across the live instances of
        ``model`` (or all models) — a scheduler state feature."""
        return sum(i.engine.prefill_backlog_tokens
                   for i in self.live(model))

    def _sweep(self) -> None:
        """DRAINING instances with no resident work → RETIRED; the engine
        (its KV slot cache) is dropped so the memory really frees."""
        for model, lst in self.instances.items():
            keep = []
            retired_any = False
            for inst in lst:
                if inst.state == DRAINING and inst.n_resident == 0:
                    inst.state = RETIRED
                    inst.engine = None
                    retired_any = True
                    if self.kv_blocks_free is not None:
                        # the instance's KV block grant returns to the
                        # shared budget
                        self.kv_blocks_free += inst.kv_blocks
                    inst.kv_blocks = 0
                    self.retired.append(inst)
                else:
                    keep.append(inst)
            self.instances[model] = keep
            # drop weight/jit templates no live instance of the model
            # spans any more, so those devices' memory really frees
            # (live instances hold their own references: always safe)
            spans = {i.devices for i in keep}
            for key in [k for k in self._templates
                        if k[0] == model and k[1] not in spans]:
                self._templates.pop(key)
            if not keep:
                if retired_any:
                    # per-model preemption bookkeeping dies with the
                    # last instance: a model respawned after scale_to(0)
                    # must not start inside a stale cooldown window or
                    # inherit an inflated preempt count
                    self.preempts_by_model[model] = 0
                    self._last_preempt_step.pop(model, None)

    # ---- router (docs/RUNTIME.md admission rules) ------------------------
    def submit(self, model: str, prompt: np.ndarray, slo_ms: float = 1000.0,
               max_new_tokens: int = 8,
               submit_s: Optional[float] = None) -> int:
        if model not in self.configs:
            raise KeyError(f"unknown model {model!r}; "
                           f"pool serves {sorted(self.configs)}")
        rid = self._next_rid
        self._next_rid += 1
        req = PoolRequest(rid, model, np.asarray(prompt, np.int32), slo_ms,
                          max_new_tokens,
                          self.now() if submit_s is None else submit_s)
        req.lifecycle = RequestLifecycle(rid, req.submit_s)
        heapq.heappush(self.queues[model],
                       (req.deadline_s, next(_seq), req))
        return rid

    # ---- cancellation (docs/RUNTIME.md §11) ------------------------------
    def _dequeue(self, model: str, request_id: int
                 ) -> Optional[PoolRequest]:
        """Remove ``request_id`` from the model's EDF queue EAGERLY
        (swap-pop + re-heapify). Eager removal is what fixes queue-head
        starvation on cancellation: a cancelled head used to sit in the
        heap blocking FIFO admission of everything behind it until an
        admission pass happened to reject it."""
        q = self.queues[model]
        for qi, (_, _, req) in enumerate(q):
            if req.request_id == request_id:
                q[qi] = q[-1]
                q.pop()
                heapq.heapify(q)
                return req
        return None

    def cancel(self, request_id: int) -> Optional[PoolResult]:
        """Tear down ``request_id`` wherever it lives — the EDF queue
        (including a preempted snapshot awaiting re-admission), an
        engine's waiting list, a mid-prefill slot, or a decoding slot —
        freeing its blocks synchronously. Returns the cancelled
        ``PoolResult`` (partial tokens included), or ``None`` when the
        id is unknown or already terminal (cancel after finish is a
        no-op: the race is inherent to streaming clients)."""
        for model in self.queues:
            req = self._dequeue(model, request_id)
            if req is not None:
                # a preempted snapshot carries its pre-eviction tokens
                snap = req.resume
                if snap is not None and snap.swapped:
                    # the snapshot will never resume: its host blocks go
                    # back to the source engine's host tier (nothing to
                    # free when that engine is already retired — the
                    # host pool died with it)
                    src = self._swap_source(model, snap)
                    if src is not None:
                        src.engine.allocator.host_free(snap.host_blocks)
                    tokens = np.asarray(snap.tokens, np.int32)
                elif snap is not None:
                    tokens = np.asarray(snap.seq_tokens[snap.base_len:],
                                        np.int32)
                else:
                    tokens = np.zeros((0,), np.int32)
                return self._finish_cancel(req, None, tokens)
        for inst in self.live():
            for erid, req in list(inst.requests.items()):
                if req.request_id != request_id:
                    continue
                r = inst.engine.cancel(erid)
                if r is None:  # engine already finished it this step
                    return None
                inst.requests.pop(erid, None)
                return self._finish_cancel(req, inst, r.tokens)
        return None

    def _finish_cancel(self, req: PoolRequest,
                       inst: Optional["ModelInstance"],
                       tokens: np.ndarray) -> PoolResult:
        now = self.now()
        res = PoolResult(req.request_id, req.model,
                         inst.instance_id if inst is not None else -1,
                         tokens, req.submit_s, req.admit_s, now,
                         req.slo_ms, utility=0.0, cancelled=True,
                         first_token_s=req.first_token_s)
        self.n_cancelled += 1
        self._results[req.model].append(res)
        if req.lifecycle is not None and not req.lifecycle.terminal:
            req.lifecycle.to(lifecycle.CANCELLED, now)
        self._emit(req, "cancelled", tokens=[int(t) for t in tokens])
        self._listeners.pop(req.request_id, None)
        return res

    def queue_len(self, model: str) -> int:
        return len(self.queues[model])

    def admission_headroom(self, model: str, prompt_len: int,
                           max_new_tokens: int) -> Dict[str, float]:
        """Backpressure signal for a front-end (docs/RUNTIME.md §11):
        could a request of this shape start NOW, and if not, when is it
        worth retrying? ``admissible_now`` is the engines' real admission
        gate (free slot + reservable blocks under the slot cap);
        ``retry_after_s`` prices the work queued ahead — prefill backlog
        plus queued requests' footprints plus this request's own — with
        the calibrated per-token iteration cost, falling back to a
        queue-depth heuristic before calibration."""
        cap = self.slot_caps[model]
        admissible_now = any(
            cap - i.n_resident > 0
            and i.engine.admissible(prompt_len, max_new_tokens)
            for i in self.running(model))
        qdepth = len(self.queues[model])
        backlog = self.prefill_backlog_tokens(model)

        def _queued_work(r: PoolRequest) -> int:
            if r.resume is None:
                return len(r.prompt) + r.max_new_tokens
            # a preempted snapshot owes its REMAINING decode tokens (not
            # the original budget — tokens already emitted are not work
            # ahead of the caller), plus the full context re-prefill in
            # recompute mode; swapped snapshots skip recompute entirely,
            # so their context contributes nothing
            ctx = 0 if r.resume.swapped else len(r.resume.seq_tokens)
            return ctx + r.resume.max_new

        queued_tokens = sum(_queued_work(r)
                            for _, _, r in self.queues[model])
        work = backlog + queued_tokens
        if not admissible_now:
            work += prompt_len + max_new_tokens
        base, per_tok = self.token_cost()
        if per_tok > 0.0:
            retry_s = (base + work * per_tok) / 1000.0
        else:
            retry_s = 0.05 * (1 + qdepth)
        return {
            "admissible_now": float(admissible_now),
            "queue_depth": float(qdepth),
            "backlog_tokens": float(backlog + queued_tokens),
            "retry_after_s": float(min(max(retry_s, 0.05), 30.0)),
        }

    def oldest_slack_ms(self, model: str) -> float:
        """Remaining SLO budget of the most urgent waiting request."""
        if not self.queues[model]:
            return float("inf")
        return (self.queues[model][0][0] - self.now()) * 1000.0

    def _request_blocks(self, eng: ContinuousBatchingEngine,
                        req: PoolRequest) -> int:
        """Worst-case block need of ``req`` on ``eng`` — the resumed
        context for a preempted sequence, the fresh-prompt shape
        otherwise."""
        if req.resume is not None:
            return eng.resume_blocks(req.resume)
        return eng.request_blocks(len(req.prompt), req.max_new_tokens)

    def _never_admissible(self, model: str, req: PoolRequest) -> bool:
        """True when ``req``'s worst-case block reservation exceeds every
        grant this pool could ever field for ``model`` — the largest
        live instance AND the (unclamped, optimistic) grant a future
        spawn would take. Such a request can never leave the EDF queue,
        so the router rejects it up front."""
        insts = self.running(model)
        if not insts:
            return False
        need = self._request_blocks(insts[0].engine, req)
        cap = max(i.engine.allocator.n_blocks for i in insts)
        return need > max(cap, self._spawn_grant())

    # ---- SLO-aware preemption (docs/RUNTIME.md §8) -----------------------
    def _try_preempt(self, model: str, req: PoolRequest,
                     now: float) -> bool:
        """Preempt one resident of ``model`` to make room for the urgent
        waiting request ``req``. Fires only when (a) no instance can
        admit ``req``, (b) its slack no longer covers its predicted
        service time (calibrated contention model), and (c) a victim
        exists whose slack exceeds the urgent slack by the hysteresis
        margin, was not preempted too often already, is not mid-chunk
        prefill, and whose eviction actually makes ``req`` admissible.
        At most one preemption per model per cooldown window."""
        last = self._last_preempt_step.get(model)
        if last is not None and \
                self.n_steps - last < self.preempt_cooldown_steps:
            return False
        t1, c = self.contention()
        if t1 <= 0.0:
            return False  # uncalibrated: no service-time prediction yet
        need_ms = req.max_new_tokens * lm.predicted_iter_ms(
            t1, c, max(1, self.busy_count()))
        slack_ms = (req.deadline_s - now) * 1000.0
        if slack_ms >= need_ms:
            return False  # not urgent: waiting for an eviction is fine
        best = None
        for inst in self.running(model):
            eng = inst.engine
            if req.resume is not None and req.resume.swapped \
                    and id(eng) != req.resume.host_engine_id:
                continue  # a swapped head only fits its source engine
            for slot, erid, freeable in eng.preemption_candidates():
                vreq = inst.requests.get(erid)
                if vreq is None or vreq.n_preempted >= self.max_preemptions:
                    continue
                vslack_ms = (vreq.deadline_s - now) * 1000.0
                if vslack_ms <= slack_ms + self.preempt_margin_ms:
                    continue  # hysteresis: victim must be clearly lazier
                if eng.allocator.n_available + freeable \
                        < self._request_blocks(eng, req):
                    continue  # eviction would not make req admissible
                if best is None or vslack_ms > best[0]:
                    best = (vslack_ms, inst, slot, erid)
        if best is None:
            return False
        _, inst, slot, erid = best
        mode = self._pick_preempt_mode(inst.engine, slot)
        snapshot = inst.engine.preempt(slot, requeue=False, mode=mode)
        vreq = inst.requests.pop(erid)
        vreq.resume = snapshot
        vreq.n_preempted += 1
        if mode == "swap":
            self.n_swap_preempted += 1
        if vreq.lifecycle is not None and not vreq.lifecycle.terminal:
            # DECODE -> QUEUED, annotated with HOW the edge was taken:
            # swapped KV waits in the host tier, recompute re-prefills
            vreq.lifecycle.to(lifecycle.QUEUED, now,
                              swapped=(mode == "swap"))
        self._emit(vreq, "preempted", instance_id=inst.instance_id,
                   swapped=(mode == "swap"))
        heapq.heappush(self.queues[model],
                       (vreq.deadline_s, next(_seq), vreq))
        self.n_preempted += 1
        self.preempts_by_model[model] += 1
        self._last_preempt_step[model] = self.n_steps
        return True

    def _pick_preempt_mode(self, eng: ContinuousBatchingEngine,
                           slot: int) -> str:
        """The recompute-vs-swap decision as a COSTED choice
        (docs/RUNTIME.md §8). ``recompute`` resumes by re-prefilling the
        victim's whole context: priced with the calibrated token-cost
        fit. ``swap`` pays two PCIe-ish transfers (out now, in at
        resume): priced with the swap-bandwidth fit over observed
        transfers. Uncalibrated fits prefer swap whenever the host tier
        has room — a transfer is the only way to collect swap samples,
        and recompute cost grows quadratically with context while swap
        cost is linear in resident blocks."""
        if self.preempt_mode == "recompute" or not eng.can_swap(slot):
            return "recompute"
        if self.preempt_mode == "swap":
            return "swap"
        pos = int(eng.pos[slot])
        base, per_tok = self.token_cost()
        swap_base, per_mb = self.swap_cost()
        if per_tok <= 0.0 or per_mb <= 0.0:
            return "swap"
        recompute_ms = base + pos * per_tok
        mb = len(eng.slots[slot].blocks) \
            * eng.swap_bytes_per_block / 1e6
        swap_ms = 2.0 * (swap_base + mb * per_mb)
        return "swap" if swap_ms < recompute_ms else "recompute"

    def swap_cost(self) -> Tuple[float, float]:
        """Calibrated ``(base_ms, ms_per_mb)`` swap-transfer model over
        every live engine's observed (bytes, ms) samples
        (``latency_model.fit_swap_cost``); ``(0, 0)`` before any
        transfer has been measured."""
        samples: List[Tuple[int, float]] = []
        for i in self.live():
            samples.extend(
                getattr(i.engine, "swap_samples", [])[-_SAMPLE_WINDOW:])
        if len(samples) < 4:
            return 0.0, 0.0
        return lm.fit_swap_cost(samples[-_SAMPLE_WINDOW:])

    def _swap_source(self, model: str,
                     snap: PreemptedRequest) -> Optional[ModelInstance]:
        """The instance whose engine's host pool holds ``snap``'s
        swapped blocks (None once it is retired)."""
        for inst in self.instances[model]:
            if inst.engine is not None \
                    and id(inst.engine) == snap.host_engine_id:
                return inst
        return None

    def _repin_swap(self, model: str, req: PoolRequest) -> None:
        """A swap snapshot can only resume on the engine holding its
        host blocks. When that engine is draining or gone, convert the
        snapshot back to recompute so the request stays routable —
        releasing the host blocks while the engine still exists, or
        rebuilding from the carried tokens after it is retired (the host
        pool died with it)."""
        snap = req.resume
        if snap is None or not snap.swapped:
            return
        src = self._swap_source(model, snap)
        if src is None:
            req.resume = to_recompute(snap)
        elif src.state != RUNNING:
            req.resume = src.engine.release_swap(snap)

    def _reject(self, req: PoolRequest) -> PoolResult:
        now = self.now()
        res = PoolResult(req.request_id, req.model, -1,
                         np.zeros((0,), np.int32), req.submit_s, now, now,
                         req.slo_ms, utility=0.0, rejected=True)
        self.n_rejected += 1
        self._results[req.model].append(res)
        # admission rejection is an EVENT, not a silent queue drop: a
        # streaming front-end relays it instead of holding the client
        # open against a request that will never run (docs/RUNTIME.md §11)
        if req.lifecycle is not None and not req.lifecycle.terminal:
            req.lifecycle.to(lifecycle.REJECTED, now)
        self._emit(req, "rejected", slo_ms=req.slo_ms)
        self._listeners.pop(req.request_id, None)
        return res

    def route(self) -> List[PoolResult]:
        """Admit waiting requests, earliest absolute deadline first, into
        the least-loaded RUNNING instance of their model; under
        ``strict_admission`` requests that can no longer meet their
        deadline are rejected instead of occupying a slot
        (docs/RUNTIME.md admission rules). Returns the rejections."""
        rejected: List[PoolResult] = []
        now = self.now()
        t1, c = self.contention()
        #: blocks promised to requests routed THIS pass that their engine
        #: has not reserved yet (reservation happens inside engine.admit,
        #: at the next iteration boundary) — without this debit a single
        #: route() pass could admit several EDF heads against the same
        #: free blocks
        pending: Dict[int, int] = {}
        for model, q in self.queues.items():
            cap = self.slot_caps[model]
            open_insts = [i for i in self.running(model)
                          if cap - i.n_resident > 0]
            while q:
                deadline_s, _, req = q[0]
                # swap snapshots resume only on their source engine; a
                # drained/retired source downgrades them to recompute
                # BEFORE any admissibility question is asked
                self._repin_swap(model, req)
                if self.strict_admission:
                    hopeless = now > deadline_s
                    if not hopeless and t1 > 0.0:
                        need_ms = req.max_new_tokens * lm.predicted_iter_ms(
                            t1, c, max(1, self.busy_count() + 1))
                        hopeless = now + need_ms / 1000.0 > deadline_s
                    if hopeless:
                        heapq.heappop(q)
                        rejected.append(self._reject(req))
                        continue
                def _open():
                    return [i for i in self.running(model)
                            if cap - i.n_resident > 0]

                def _cands():
                    insts = open_insts
                    if req.resume is not None and req.resume.swapped:
                        # swapped KV is resident in ONE engine's host
                        # pool: only that engine can re-map it
                        insts = [i for i in insts if id(i.engine)
                                 == req.resume.host_engine_id]
                    return [i for i in insts
                            if i.engine.admissible(
                                len(req.prompt), req.max_new_tokens,
                                pending.get(i.instance_id, 0),
                                resume=req.resume, prompt=req.prompt)]

                # engines additionally gate on free KV blocks — a slot
                # is only admissible when the request's worst-case block
                # need is reservable (docs/RUNTIME.md §7)
                cands = _cands() if open_insts else []
                if not cands and self.preemption and \
                        self._try_preempt(model, req, now):
                    # the victim's slot (and blocks) freed synchronously;
                    # its instance may now admit the urgent request
                    open_insts = _open()
                    cands = _cands()
                if not open_insts and not cands:
                    break
                if not cands:
                    if self._never_admissible(model, req):
                        # no current or future grant could ever hold the
                        # reservation: reject instead of livelocking the
                        # EDF head (and everything behind it) forever
                        heapq.heappop(q)
                        rejected.append(self._reject(req))
                        continue
                    break
                if self.prefix_cache:
                    # prefix affinity (docs/RUNTIME.md §7): same-prefix
                    # requests prefer the instance whose cache already
                    # holds their prefix (hit tokens first, least-loaded
                    # as the tie-break), so shared prompts concentrate
                    # instead of re-prefilling on every instance
                    inst = max(cands, key=lambda i: (
                        i.engine.cached_prefix_tokens(
                            req.resume.seq_tokens if req.resume is not None
                            else req.prompt,
                            prepadded=req.resume is not None),
                        cap - i.n_resident))
                else:
                    inst = max(cands, key=lambda i: cap - i.n_resident)
                heapq.heappop(q)
                pending[inst.instance_id] = \
                    pending.get(inst.instance_id, 0) \
                    + self._request_blocks(inst.engine, req)
                if req.resume is not None:
                    erid = inst.engine.submit_resume(req.resume)
                    req.resume = None
                else:
                    erid = inst.engine.submit(req.prompt,
                                              req.max_new_tokens)
                req.admit_s = now
                inst.requests[erid] = req
                self.admission_log.append((req.request_id,
                                           inst.instance_id))
                if cap - inst.n_resident <= 0:
                    open_insts.remove(inst)
        return rejected

    # ---- iteration -------------------------------------------------------
    def _finish(self, inst: ModelInstance,
                r: ContinuousResult) -> PoolResult:
        req = inst.requests.pop(r.request_id)
        tokens = r.tokens
        now = self.now()
        hist = self._results[req.model]
        # throughput term of Eq. 3: this model's completions per second
        # over a recent window (the streaming analogue of the simulator's
        # per-session throughput); the window always spans at least this
        # request's own lifetime so an empty history cannot fake an
        # arbitrarily high rate
        recent = [r.finish_s for r in hist[-32:] if not r.rejected] + [now]
        span_s = max(now - min(recent), now - req.submit_s, 1e-3)
        thr = len(recent) / span_s
        u = utility(max(thr, 1e-3), max(now - req.submit_s, 1e-4),
                    req.slo_ms / 1000.0, max(1, self.m_c(req.model)))
        res = PoolResult(req.request_id, req.model, inst.instance_id,
                         tokens, req.submit_s, req.admit_s, now, req.slo_ms,
                         utility=0.0 if r.cancelled else u,
                         cancelled=bool(r.cancelled),
                         first_token_s=req.first_token_s)
        inst.n_served += 1
        hist.append(res)
        # client-observed timing aggregates (satellite of RUNTIME §11):
        # recorded on the pool clock at completion, so they exist with or
        # without an HTTP front-end in the loop. Cancelled results are
        # EXCLUDED: a disconnect storm's partial timings would otherwise
        # drag ttft/tpot p99 below what completed clients observed, even
        # though cancellations are already excluded from SLO attainment
        if res.first_token_s >= 0 and not res.cancelled:
            self.ttft_samples.append(res.ttft_ms)
            if res.tpot_ms >= 0:
                self.tpot_samples.append(res.tpot_ms)
            if len(self.ttft_samples) > 2 * _SAMPLE_WINDOW:
                del self.ttft_samples[:-_SAMPLE_WINDOW]
            if len(self.tpot_samples) > 2 * _SAMPLE_WINDOW:
                del self.tpot_samples[:-_SAMPLE_WINDOW]
        if res.cancelled:
            self.n_cancelled += 1
        if req.lifecycle is not None and not req.lifecycle.terminal:
            req.lifecycle.to(lifecycle.CANCELLED if res.cancelled
                             else lifecycle.FINISHED, now)
        self._emit(req, "finished", tokens=[int(t) for t in tokens],
                   latency_ms=res.latency_ms, utility=u,
                   truncated=bool(r.truncated),
                   n_preempted=int(r.n_preempted))
        self._listeners.pop(req.request_id, None)
        return res

    def step(self) -> List[PoolResult]:
        """One pool iteration: sweep retirements, route admissions, then
        run ONE decode iteration on every busy live instance. Returns the
        requests that finished (or were rejected) this iteration. Each
        part runs under its profiler span (``repro.pool.*``,
        ``tracing.py``)."""
        with span("repro.pool.sweep"):
            self._sweep()
        with span("repro.pool.route"):
            out: List[PoolResult] = list(self.route())
        busy = [i for i in self.live()
                if i.engine.active_slots or i.engine.waiting]
        if not busy:
            self.n_steps += 1
            return out
        pure_decode = not any(i.engine.prefill_backlog_tokens
                              for i in busy)
        t0 = time.perf_counter()
        for inst in busy:
            with span("repro.pool.instance_step"):
                done = inst.engine.step()
            with span("repro.pool.finish"):
                out.extend(self._finish(inst, r) for r in done)
        iter_ms = (time.perf_counter() - t0) * 1000.0
        with span("repro.pool.calibrate"):
            self._calibrate(busy, pure_decode, iter_ms)
        self.n_steps += 1
        return out

    def _calibrate(self, busy: List[ModelInstance], pure_decode: bool,
                   iter_ms: float) -> None:
        """Feed one iteration's wall time to the latency fits.

        The latency a sequence experiences per decode token is the wall
        time of the WHOLE pool iteration (every busy instance steps once
        before any sequence advances again) — that is the quantity the
        contention model calibrates against the overlap level. Steps
        that do prefill-chunk work are excluded from the CONTENTION fit
        (their cost scales with chunk tokens, not overlap) but feed the
        token-cost fit, which prices exactly that."""
        overlap = len(busy)
        compiled = any(i.engine.last_step_compiled for i in busy)
        if not compiled:
            # (tokens processed, wall ms) — the fit behind the
            # per-iteration token-budget knob (docs/RUNTIME.md §8);
            # compile iterations would swamp the slope
            sample = (sum(i.engine.last_step_tokens for i in busy),
                      iter_ms)
            self.token_samples.append(sample)
            if len(self.token_samples) > 2 * _SAMPLE_WINDOW:
                del self.token_samples[:-_SAMPLE_WINDOW]
            degrees = {i.tp_degree for i in busy}
            if len(degrees) == 1:
                # degree-homogeneous iteration: attributable to ONE
                # layout, so it also feeds that degree's token-cost fit
                bucket = self.tp_token_samples.setdefault(
                    degrees.pop(), [])
                bucket.append(sample)
                if len(bucket) > 2 * _SAMPLE_WINDOW:
                    del bucket[:-_SAMPLE_WINDOW]
        if pure_decode and not compiled:
            self.contention_samples.append((overlap, iter_ms))
            self.occupancy_samples.append(
                (sum(i.n_resident for i in busy),
                 sum(i.engine.kv_used_tokens for i in busy)))
            if len(self.contention_samples) > 2 * _SAMPLE_WINDOW:
                # long-lived serving loops step for hours: keep only the
                # trailing window the calibration fits ever read
                del self.contention_samples[:-_SAMPLE_WINDOW]
            if len(self.occupancy_samples) > 2 * _SAMPLE_WINDOW:
                del self.occupancy_samples[:-_SAMPLE_WINDOW]
        if self.predictor is not None and pure_decode:
            for inst in busy:
                self.predictor.observe(
                    engine_features(self.configs[inst.model],
                                    self.m_c(inst.model),
                                    inst.n_resident, overlap),
                    iter_ms / 1000.0)

    def _work_pending(self) -> bool:
        return any(self.queues.values()) \
            or any(i.n_resident for i in self.live())

    def _can_progress(self) -> bool:
        """Stepping can still move work: something is resident on a live
        instance, or a queued model has a RUNNING instance to route to.
        Queued work with every instance retired is NOT progressable —
        the caller must scale up first."""
        if any(i.n_resident for i in self.live()):
            return True
        return any(q and self.running(m) for m, q in self.queues.items())

    def run_until_drained(self, max_steps: int = 10_000
                          ) -> List[PoolResult]:
        """Step until every queue and instance is empty (tests/benchmarks;
        the serving loop calls ``step()`` directly).

        Raises ``RuntimeError`` when ``max_steps`` is exhausted with work
        still pending — a silent partial return here made benchmarks read
        partial completions as full drains. Queued work that CANNOT
        progress (its model has no RUNNING instance) returns normally
        instead of spinning: everything drainable was drained."""
        done: List[PoolResult] = []
        while max_steps > 0 and self._work_pending():
            if not self._can_progress():
                break
            done.extend(self.step())
            max_steps -= 1
        self._sweep()
        if self._work_pending() and self._can_progress():
            queued = {m: len(q) for m, q in self.queues.items() if q}
            resident = sum(i.n_resident for i in self.live())
            raise RuntimeError(
                f"run_until_drained: max_steps exhausted with work still "
                f"pending (queued={queued}, resident={resident}) — raise "
                f"max_steps or treat the workload as undrainable")
        return done

    def warmup(self, prompt_lens: Tuple[int, ...] = (8, 20),
               seed: int = 0) -> None:
        """Compile the serving shapes before traffic: one prompt per
        length bucket per model (at an effectively-infinite SLO), drained
        to completion, then every decode form of each live engine
        (``warmup_decode``), then metrics reset — so neither compile time
        nor the warmup traffic pollutes SLO stats or the contention fit.
        Callers scale first; models at m_c = 0 are skipped."""
        rng = np.random.default_rng(seed)
        submitted = False
        for m, cfg in self.configs.items():
            if self.m_c(m) == 0:
                continue
            for n in prompt_lens:
                self.submit(m, rng.integers(1, cfg.vocab_size, n).astype(
                    np.int32), slo_ms=600_000.0, max_new_tokens=2)
                submitted = True
        if submitted:
            self.run_until_drained()
        for inst in self.live():
            inst.engine.warmup_decode()
        self.reset_metrics()

    # ---- accounting ------------------------------------------------------
    def reset_metrics(self) -> None:
        """Clear serving metrics (results, admission log, counters,
        calibration samples) but keep instances and warm jit caches —
        called after a warmup pass so compile time pollutes neither the
        SLO stats nor the contention fit."""
        self._results = {m: [] for m in self.configs}
        self.admission_log = []
        self.contention_samples = []
        self.occupancy_samples = []
        self.token_samples = []
        self.tp_token_samples = {}
        self.ttft_samples = []
        self.tpot_samples = []
        self.n_rejected = 0
        self.n_cancelled = 0
        self.n_preempted = 0
        self.n_swap_preempted = 0
        self.preempts_by_model = {m: 0 for m in self.configs}
        self._last_preempt_step = {}
        self.n_steps = 0
        for lst in self.instances.values():
            for inst in lst:
                inst.n_served = 0
        self._t0 = time.perf_counter()

    def contention(self) -> Tuple[float, float]:
        """Calibrated ``(t1_ms, c)`` from the measured samples
        (``latency_model.fit_contention``); ``(0, 0)`` before warmup."""
        if len(self.contention_samples) < 8:
            return 0.0, 0.0
        return lm.fit_contention(self.contention_samples[-_SAMPLE_WINDOW:])

    def token_cost(self, tp_degree: Optional[int] = None
                   ) -> Tuple[float, float]:
        """Calibrated ``(base_ms, per_token_ms)`` iteration-cost model
        (``latency_model.fit_token_cost``); ``(0, 0)`` before warmup.
        Prices the per-iteration token budget for the scheduler guard.

        ``tp_degree`` selects that degree's fit (measured only on
        degree-homogeneous iterations); a degree without enough samples
        yet falls back to the global fit, and the guard layers the
        analytic collective term on top (docs/RUNTIME.md §10)."""
        if tp_degree is not None:
            bucket = self.tp_token_samples.get(tp_degree, [])
            if len(bucket) >= 8:
                return lm.fit_token_cost(bucket[-_SAMPLE_WINDOW:])
        if len(self.token_samples) < 8:
            return 0.0, 0.0
        return lm.fit_token_cost(self.token_samples[-_SAMPLE_WINDOW:])

    # ---- KV occupancy (docs/RUNTIME.md §7) -------------------------------
    def kv_used_tokens(self, model: Optional[str] = None) -> int:
        """Σ cache tokens resident sequences occupy right now, over the
        live instances of ``model`` (or all models)."""
        return sum(i.engine.kv_used_tokens for i in self.live(model))

    def occupancy_tokens_per_seq(self) -> float:
        """Measured mean KV tokens per resident sequence
        (``latency_model.fit_occupancy``); 0.0 before calibration."""
        if len(self.occupancy_samples) < 8:
            return 0.0
        return lm.fit_occupancy(self.occupancy_samples[-_SAMPLE_WINDOW:])

    def prefix_hit_rate(self) -> float:
        """Prompt tokens served from prefix caches as a fraction of all
        prompt tokens processed, aggregated over live instances — a
        scheduler state feature (docs/RUNTIME.md §7)."""
        live = self.live()
        hit = sum(getattr(i.engine, "n_prefix_hit_tokens", 0)
                  for i in live)
        total = hit + sum(getattr(i.engine, "n_prefill_chunk_tokens", 0)
                          for i in live)
        return hit / total if total else 0.0

    def spec_accept_rate(self) -> float:
        """Draft tokens accepted as a fraction of draft tokens proposed,
        aggregated over live instances — the scheduler state feature
        behind the k axis (docs/RUNTIME.md §9). 0.0 before any
        speculative step (and always, for spec-off pools)."""
        live = self.live()
        acc = sum(getattr(i.engine, "n_spec_accepted", 0) for i in live)
        prop = sum(getattr(i.engine, "n_spec_proposed", 0) for i in live)
        return acc / prop if prop else 0.0

    def kv_shared_frac(self) -> float:
        """Fraction of live block mappings backed by a block another
        resident sequence also maps, pool-wide: 1 - distinct/logical.
        The guard uses it to price *effective* blocks — refcounted
        blocks charge the shared budget once."""
        logical = distinct = 0
        for i in self.live():
            lg, d = i.engine.kv_block_mapping()
            logical += lg
            distinct += d
        return 1.0 - distinct / logical if logical else 0.0

    def kv_occupancy(self) -> Dict[str, float]:
        """Real occupancy of the shared KV budget — what grounds the
        ``PoolScheduler`` Eq.-4 guard. Budget
        fields are 0 for unlimited budgets. ``allocated_tokens`` counts
        a refcount-shared block ONCE (each engine reports distinct live
        blocks), so the gap to the logical ``used_tokens`` is exactly
        what prefix sharing saves."""
        budget_blocks = self.kv_block_budget or 0
        committed = sum(i.kv_blocks for i in self.live())
        # host-tier occupancy across live engines (0 everywhere when no
        # engine carries a host pool)
        host_blocks = host_free = host_live = host_cached = 0
        for i in self.live():
            a = i.engine.allocator
            host_blocks += a.n_host_blocks
            host_free += a.n_host_free
            host_live += a.n_host_live
            host_cached += a.n_host_cached
        return {
            "used_tokens": float(self.kv_used_tokens()),
            "allocated_tokens": float(sum(
                i.engine.kv_allocated_tokens for i in self.live())),
            "budget_tokens": float(budget_blocks * self.block_size),
            "free_blocks": float(self.kv_blocks_free or 0),
            "committed_blocks": float(committed),
            "tokens_per_seq": self.occupancy_tokens_per_seq(),
            "shared_frac": self.kv_shared_frac(),
            "prefix_hit_rate": self.prefix_hit_rate(),
            "host_blocks": float(host_blocks),
            "host_free": float(host_free),
            "host_live": float(host_live),
            "host_cached": float(host_cached),
            "host_frac": float((host_live + host_cached) / host_blocks)
            if host_blocks else 0.0,
        }

    def slot_ms(self, model: str) -> float:
        """Eq. 1 for the live allocation: t_i = Σ SLO of the model's
        resident requests / m_c. The PoolScheduler re-decides once per
        slot (docs/RUNTIME.md Eq.-1 accounting)."""
        slo_sum = sum(i.slo_sum_ms for i in self.instances[model]
                      if i.state in (RUNNING, DRAINING))
        return slo_sum / max(1, self.m_c(model))

    def results(self, model: str) -> List[PoolResult]:
        """All finished/rejected results for ``model`` so far."""
        return list(self._results[model])

    def states(self, model: str) -> List[str]:
        return [i.state for i in self.instances[model]] + \
            [i.state for i in self.retired if i.model == model]

    def report(self) -> Dict[str, Dict[str, float]]:
        """Per-model serving metrics over the pool's lifetime."""
        out: Dict[str, Dict[str, float]] = {}
        for model, results in self._results.items():
            # cancelled requests left on their client's initiative: they
            # are reported, but neither served nor violated — attainment
            # is over the requests the pool was actually asked to finish
            considered = [r for r in results if not r.cancelled]
            served = [r for r in considered if not r.rejected]
            viol = sum(1 for r in considered if r.violated)
            lats = [r.latency_ms for r in served]
            out[model] = {
                "served": float(len(served)),
                "rejected": float(len(considered) - len(served)),
                "cancelled": float(len(results) - len(considered)),
                "violations": float(viol),
                "slo_attainment": 1.0 - viol / max(1, len(considered)),
                "mean_latency_ms": float(np.mean(lats)) if lats else 0.0,
                "mean_utility": float(np.mean(
                    [r.utility for r in served])) if served else 0.0,
                "m_c": float(self.m_c(model)),
                "tp_degree": float(self.tp_degrees.get(model, 1)),
                "queued": float(len(self.queues[model])),
                "preempted": float(self.preempts_by_model.get(model, 0)),
            }
        return out

    def stats(self) -> Dict[str, float]:
        t1, c = self.contention()
        base, per_tok = self.token_cost()
        swap_base, per_mb = self.swap_cost()
        out = {
            "n_steps": float(self.n_steps),
            "live_instances": float(self.total_live()),
            "devices_in_use": float(self.devices_in_use()),
            "retired_instances": float(len(self.retired)),
            "n_rejected": float(self.n_rejected),
            "n_cancelled": float(self.n_cancelled),
            "n_preempted": float(self.n_preempted),
            "n_swap_preempted": float(self.n_swap_preempted),
            "prefill_backlog_tokens": float(self.prefill_backlog_tokens()),
            "contention_t1_ms": t1,
            "contention_c": c,
            "token_base_ms": base,
            "token_per_ms": per_tok,
            "swap_base_ms": swap_base,
            "swap_ms_per_mb": per_mb,
            "spec_accept_rate": self.spec_accept_rate(),
            # work the live instances' engines did: rows of the decode
            # calls, prefill rows and their padding, steps that compiled
            **{k: float(sum(getattr(i.engine, k) for i in self.live()))
               for k in ENGINE_COUNTERS},
            # client-observed timing percentiles over the trailing window
            # (pool clock, HTTP-independent); 0.0 before any completion
            "ttft_ms_p50": float(np.percentile(self.ttft_samples, 50))
            if self.ttft_samples else 0.0,
            "ttft_ms_p99": float(np.percentile(self.ttft_samples, 99))
            if self.ttft_samples else 0.0,
            "tpot_ms_p50": float(np.percentile(self.tpot_samples, 50))
            if self.tpot_samples else 0.0,
            "tpot_ms_p99": float(np.percentile(self.tpot_samples, 99))
            if self.tpot_samples else 0.0,
        }
        out.update({f"kv_{k}": v for k, v in self.kv_occupancy().items()})
        return out
