"""Serving engines: continuous batching over the DecodeState protocol,
plus the fixed-batch baseline.

``ContinuousBatchingEngine`` is the production path for *all five*
workload families (lm/dense, moe, ssm, hybrid, vlm, audio): requests are
submitted to a queue, the scheduler composes sarathi-style mixed steps
(every in-flight decode + a bounded chunk of every in-flight prefill),
and the engine executes each step as fixed-shape jitted calls against
the slotted decode state — one batched (n_slots, 1) decode plus one
single-row (1, prefill_chunk) forward per prefilling slot, so prefill
work never multiplies across idle rows.  The engine never branches on a
family: the model's DecodeState adapter (models/decode_state.py) lays
out attention KV, recurrent conv/SSD state, and read-only cross context
as one pytree with per-row primitives, and the layers implement the
row-masked ragged write (``n_valid``) so idle / preempted / finished
rows' state is untouched by a mixed step.  Requests with read-only
context (vlm image embeddings, audio frames) pass it to ``submit`` as
``extra``; it is projected and installed into the slot's cache row at
every (re-)admission.  Slots recycle the moment their request finishes,
so a queued request is admitted mid-run without draining the batch.
Greedy and temperature sampling are both wired through
(serve/sampling.py, shared with the static engine; per request, as a
traced per-row temperature vector — no recompilation).

``ContinuousBatchingEngine(mesh=...)`` serves **sharded**: the decode
slot axis lays out over the production mesh's ``("pod", "data")`` axes
(``launch/mesh.py`` builds the meshes; ``parallel.sharding.rules_for``
resolves the per-architecture rules), parameters and donated buffers get
``NamedSharding`` layouts, the paged bookkeeping and prefix pool
partition per slot shard, and ``sp_kv=True`` turns on the
sequence-parallel KV cache (flash-decoding combine) over ``"model"``.
The host loop, token chaining, and deferred flush are unchanged — a
``mesh=None`` engine is bitwise the single-device engine.

``StaticBatchEngine`` is the old run-to-completion engine (one prefill +
a decode loop over a fixed batch), kept purely as the correctness and
throughput baseline (benchmarks/serve_bench.py, the per-family parity
tests).

``make_prefill_step`` / ``make_serve_step`` remain the pjit-ready pure
functions used by the multi-pod dry-run and the SP-KV tests.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.configs.shapes import ShapeSpec
from repro.core import costmodel
from repro.models import decode_state
from repro.models.model import LM
from repro.parallel import axes as paxes
from repro.parallel.sharding import layout_report, rules_for
from repro.perf.measure import now
from repro.serve import sampling  # noqa: F401  (submodule import, no cycle)
from repro.serve import trace
from repro.serve.cache import PagedKVCache
from repro.serve.scheduler import Request, Scheduler, StepPlan


def make_prefill_step(model: LM) -> Callable:
    def prefill_step(params, cache, tokens, positions, extra):
        logits, cache, _ = model.forward(
            params, tokens, positions, mode="prefill", cache=cache,
            extra=extra)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok, cache

    return prefill_step


def make_serve_step(model: LM, *, sample_temperature: float = 0.0) -> Callable:
    """One decode step: append token, return next token + updated cache."""

    def serve_step(params, cache, tokens, positions, extra=None):
        logits, cache, _ = model.forward(
            params, tokens, positions, mode="decode", cache=cache,
            extra=extra)
        last = logits[:, -1]
        # deterministic gumbel sampling keyed on position for repro
        key = jax.random.fold_in(jax.random.key(0), positions[0, -1])
        temps = jnp.full((last.shape[0],), sample_temperature, jnp.float32)
        next_tok = sampling.sample_tokens(last, temps, key,
                                          any_temp=sample_temperature > 0)
        return next_tok, cache

    return serve_step


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StepRecord:
    wall_s: float
    n_decode: int
    n_prefill_tokens: int
    occupancy: float
    page_utilization: float


class StepCostModel:
    """Analytic per-step FLOPs/bytes (core/costmodel) behind
    ``ContinuousBatchingEngine.modeled_step_time``, the open-loop
    frontend's deterministic model clock.

    Decode rows are costed at a representative mid-stream cache length
    (``max_len // 2``); prefill tokens at the per-token average of a full
    ``max_len`` prefill.  These are *model* numbers, never a measurement.
    """

    def __init__(self, cfg, max_len: int):
        kv = max(1, max_len // 2)
        # per-token decode cost excludes the enc-dec audio encoder: the
        # engines run it once per request at admission (install_context),
        # so it is amortized into the prefill per-token average instead
        self.decode_flops_tok = costmodel.forward_flops(
            cfg, 1, 1, kv_len=kv, decode=True,
            include_encoder=False)["total"]
        dec = costmodel.step_hbm_bytes(
            cfg, ShapeSpec("serve_decode", kv, 1, "decode"))
        self.decode_param_bytes = dec.get("params", 0.0)
        self.decode_cache_bytes_row = dec.get("cache", 0.0)
        S = max(1, max_len)
        self.prefill_flops_tok = costmodel.forward_flops(cfg, 1, S)["total"] / S
        self.prefill_bytes_tok = costmodel.step_hbm_bytes(
            cfg, ShapeSpec("serve_prefill", S, 1, "prefill"))["total"] / S

    def step_cost(self, n_decode: int, n_prefill_tokens: int
                  ) -> tuple[float, float]:
        flops = (n_decode * self.decode_flops_tok
                 + n_prefill_tokens * self.prefill_flops_tok)
        # params stream through HBM once per batched decode step, not once
        # per row; per-row traffic is the row's own cache read
        bytes_ = ((self.decode_param_bytes if n_decode else 0.0)
                  + n_decode * self.decode_cache_bytes_row
                  + n_prefill_tokens * self.prefill_bytes_tok)
        return flops, bytes_


@dataclasses.dataclass
class EngineStats:
    steps: List[StepRecord] = dataclasses.field(default_factory=list)
    generated_tokens: int = 0
    wall_s: float = 0.0
    # prompt tokens whose prefill was skipped via the prefix cache
    # (mirrors Scheduler.prefix_hit_tokens)
    prefix_hit_tokens: int = 0
    # speculative decoding: draft tokens fed to verify steps, and how
    # many of them the greedy acceptance rule kept (the bonus token at
    # the frontier is a normal sample, counted in generated_tokens but
    # never here) — accept_rate = accepted / drafted
    drafted_tokens: int = 0
    accepted_draft_tokens: int = 0

    def summary(self) -> Dict[str, float]:
        accept_rate = (self.accepted_draft_tokens / self.drafted_tokens
                       if self.drafted_tokens else 0.0)
        if not self.steps:
            # an empty drain (e.g. an open-loop tail that completed zero
            # requests) must still return the FULL key set — 0.0 rates,
            # never a KeyError or a divide-by-zero downstream — plus a
            # note so reports can surface why everything is zero
            return {"steps": 0, "generated_tokens": 0, "tok_per_s": 0.0,
                    "step_ms_p50": 0.0, "step_ms_p95": 0.0,
                    "mean_occupancy": 0.0, "mean_page_utilization": 0.0,
                    "prefix_hit_tokens": self.prefix_hit_tokens,
                    "prefix_hit_rate": 0.0,
                    "drafted_tokens": self.drafted_tokens,
                    "accepted_draft_tokens": self.accepted_draft_tokens,
                    "accept_rate": accept_rate,
                    "note": "zero steps executed"}
        walls = sorted(s.wall_s for s in self.steps)
        prefill_tokens = sum(s.n_prefill_tokens for s in self.steps)
        prompt_total = prefill_tokens + self.prefix_hit_tokens

        def pct(p):
            return walls[min(len(walls) - 1, int(p * len(walls)))]

        return {
            "steps": len(self.steps),
            "generated_tokens": self.generated_tokens,
            "tok_per_s": (self.generated_tokens / self.wall_s
                          if self.wall_s else 0.0),
            "step_ms_p50": pct(0.50) * 1e3,
            "step_ms_p95": pct(0.95) * 1e3,
            "mean_occupancy": float(np.mean(
                [s.occupancy for s in self.steps])),
            "mean_page_utilization": float(np.mean(
                [s.page_utilization for s in self.steps])),
            # fraction of all prompt tokens served from the prefix cache
            # instead of being prefilled
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_hit_rate": (self.prefix_hit_tokens / prompt_total
                                if prompt_total else 0.0),
            # speculative decoding (0 / 0.0 with spec_decode off)
            "drafted_tokens": self.drafted_tokens,
            "accepted_draft_tokens": self.accepted_draft_tokens,
            "accept_rate": accept_rate,
        }


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------
class ContinuousBatchingEngine:
    """Paged continuous-batching engine — any family with a registered
    DecodeState adapter (all five: lm/dense, moe, ssm, hybrid, vlm,
    audio).

    Usage::

        eng = ContinuousBatchingEngine(model, params, n_slots=4, max_len=64)
        rid = eng.submit(prompt_tokens, max_new_tokens=16)        # queued
        results = eng.run()          # drain; {rid: np.ndarray of tokens}

    Cross-context families pass the per-request context to ``submit``::

        eng.submit(prompt, 16, extra={"image_embeds": embeds})    # (T, d)

    ``prefix_cache=True`` enables page-table-keyed prefix caching for
    families whose decode state is token-addressable (dense/moe, vlm,
    audio): released requests' page-aligned prompt prefixes stay pooled
    (bounded by ``prefix_pool`` entries, refcounted pages, reclaimed
    LRU-first under pressure) and a matching admission copies the donor
    slot's K/V once instead of re-prefilling — preemption recovery
    included.  Recurrent families (ssm, hybrid) run with the cache off
    (a UserWarning names the family): their conv/SSD state cannot be
    truncated to a prefix.

    ``spec_decode=True`` turns on draft-verify **speculative decoding**
    (``spec_k`` = max drafted tokens per row per step): a model-free
    n-gram drafter (serve/draft.py) proposes continuations from each
    request's own prompt + committed tokens, one verify forward scores
    all ``spec_k + 1`` columns per decode row through the same
    paged-attention ragged-mask contract, and greedy acceptance commits
    the longest draft prefix matching the argmax chain plus one bonus
    token — per-row variable commit via the ``n_valid`` ragged write
    (token-addressable families rewind position counters in place;
    ssm/hybrid replay their masked recurrence with ``n_valid =
    n_accept``).  Temp-0 token streams are identical to ``spec_decode=
    False``, which itself stays byte-identical to the unspeculative
    engine; sampled (temp>0) rows never carry drafts.
    ``EngineStats.accept_rate`` reports drafted vs accepted tokens.

    ``mesh`` makes the engine **mesh-aware**: the decode slot ("batch")
    axis shards over the mesh's ``("pod", "data")`` axes and parameters /
    activations follow the resolved per-architecture rules
    (``parallel.sharding.rules_for``; pass ``rules`` to override).  The
    paged bookkeeping partitions with it — each slot shard owns its own
    page-table budget and prefix pool, and the scheduler admits,
    preempts, and matches donors shard-locally — while every donated
    device buffer (decode state, output rows, chained samples) is laid
    out with ``NamedSharding`` and pinned there across steps.
    ``sp_kv=True`` additionally shards the KV-cache sequence axis over
    ``"model"`` (the flash-decoding combine in attention).  With
    ``mesh=None`` (default) nothing changes: the single-device path is
    bitwise the unsharded engine.  A mesh whose slot axes do not divide
    ``n_slots`` serves replicated (one shard) and records the decision
    in ``sharding_meta``.

    ``analyze=True`` compiles the decode/prefill step fns at build time
    and runs the ``repro.analysis.trace`` cost-model lint over them
    (gathers on the hot path, predication density, counter-blind scans,
    f32 upcasts, missed donation, ...); the findings land in
    ``analysis_meta`` and serve_bench copies them into its Report meta.

    ``check=True`` attaches the ``repro.analysis.schedcheck`` shadow
    state machine to this engine's page tables and scheduler: every
    alloc/incref/free/admission/preemption replays through a pure-Python
    shadow first, and after every step (plus after a full ``run()``
    drain) the global invariants — refcount conservation, leak-free
    drain, slot/rid binding, prefix-pool claims — are re-derived from
    scratch.  Violations become ``Finding``s on ``engine.checker``
    (``engine.check_findings``); the tier1 serve tests run with it on
    (tests/conftest.py flips the class default).  Defaults to the class
    attribute ``_DEFAULT_CHECK`` (False) when ``None``.
    """

    #: class-level default for ``check`` (tests/conftest.py monkeypatches
    #: this to True so every tier1 serve engine is shadow-checked without
    #: touching construction sites)
    _DEFAULT_CHECK = False

    def __init__(self, model: LM, params, *, n_slots: int, max_len: int,
                 page_size: int = 16, prefill_chunk: int = 8,
                 chunk_policy: str = "fixed",
                 tbt_target_s: Optional[float] = None,
                 page_budget: Optional[int] = None,
                 eos_id: Optional[int] = None, seed: int = 0,
                 prefix_cache: bool = False, prefix_pool: int = 8,
                 mesh=None, rules=None, sp_kv: bool = False,
                 paged_kernel: Optional[bool] = None, retune: bool = False,
                 spec_decode: bool = False, spec_k: int = 4,
                 analyze: bool = False, check: Optional[bool] = None):
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        # speculative multi-token decoding (draft-verify): spec_k is the
        # max drafted tokens per decode row per step, so the compiled
        # decode step is (n_slots, spec_k + 1) wide.  With spec_decode
        # off, spec_k is forced to 0 and every compiled shape, closure,
        # and commit path is byte-identical to the unspeculative engine.
        if spec_decode and spec_k < 1:
            raise ValueError(
                f"spec_decode=True needs spec_k >= 1, got {spec_k}")
        self.spec_decode = bool(spec_decode)
        self.spec_k = int(spec_k) if self.spec_decode else 0
        # prefix caching only applies to families whose whole decode
        # state is a token prefix (attention KV + pos + installed
        # context); recurrent families run with the pool disabled and a
        # permanent 0% hit rate rather than wrong state
        if prefix_cache and not model.decode_state.prefix_cachable:
            warnings.warn(
                f"prefix_cache=True ignored: family {model.cfg.family!r} "
                "has non-token-addressable (recurrent) decode state that "
                "cannot be truncated to a prompt prefix; serving with the "
                "prefix cache off", UserWarning, stacklevel=2)
        self.prefix_cache = bool(prefix_cache
                                 and model.decode_state.prefix_cachable)
        self.mesh = mesh
        self.sp_kv = bool(sp_kv)
        self.rules = None
        self.n_shards = 1
        self.sharding_meta: Optional[Dict[str, Any]] = None
        self._cache_sharding = None
        self._slot_sharding = None
        self._out_sharding = None
        self._spec_tok_sharding = None
        if mesh is not None:
            self.rules = (dict(rules) if rules is not None
                          else rules_for(model.cfg, mesh, sp_kv=sp_kv))
            self._init_mesh_layout()
        self.kv = PagedKVCache(
            n_slots, max_len, page_size, page_budget=page_budget,
            slot_aux_tokens=model.decode_state.context_tokens(model.cfg),
            prefix_pool=prefix_pool if self.prefix_cache else 0,
            n_shards=self.n_shards)
        self.sched = Scheduler(self.kv, prefill_chunk=prefill_chunk,
                               eos_id=eos_id, chunk_policy=chunk_policy,
                               tbt_target_s=tbt_target_s,
                               spec_k=self.spec_k)
        # model-free n-gram drafter (serve/draft.py): host-side prompt
        # lookup over each request's committed tokens, feeding the
        # scheduler's draft columns.  Only built when speculation is on.
        self.drafter = None
        # rids whose drafter history misses tokens committed by no-draft
        # fast-path steps (which skip the host readback); resynced from
        # out_buf right before the rid next proposes
        self._draft_stale: set = set()
        if self.spec_decode:
            from repro.serve.draft import NGramDrafter
            self.drafter = NGramDrafter(self.spec_k,
                                        **self._drafter_throttle())
        # shadow-state checker (repro.analysis.schedcheck): pure Python,
        # no jax — wraps this (kv, sched) pair's transitions and re-derives
        # the page/slot invariants after every step.  Imported lazily so
        # check=False engines never touch the analysis subsystem.
        self.check = bool(self._DEFAULT_CHECK if check is None else check)
        self.checker = None
        if self.check:
            from repro.analysis.schedcheck import SchedChecker
            self.checker = SchedChecker.attach(self.kv, self.sched)
        # what feeds the stall-free chunk policy's per-token estimate:
        # "wall" (default) notes each step's measured wall; the open-loop
        # frontend switches this to "external" under its deterministic
        # model clock and feeds modeled step times itself
        self.step_feedback = "wall"
        self.cache = model.init_cache(n_slots, max_len)
        if mesh is not None:
            self.cache = jax.device_put(self.cache, self._cache_sharding)
        # fused paged flash-decode (kernels/paged_attention): on by
        # default — PagedKVCache guarantees max_len % page_size == 0, so
        # the cache always views as a page pool.  paged_kernel=False
        # keeps the decode closures byte-identical to the classic
        # XLA-gather engine (the bitwise-parity baseline).
        self.paged_kernel = (bool(paged_kernel)
                             if paged_kernel is not None else True)
        self._page_idx = None
        self._paged_block_pages = 1
        self.paged_meta: Optional[Dict[str, Any]] = None
        if self.paged_kernel:
            self._page_idx = jnp.asarray(self.kv.page_index_array())
            if mesh is not None:
                with paxes.sharding_ctx(mesh, self.rules):
                    self._page_idx = jax.device_put(
                        self._page_idx, paxes.named_sharding(
                            ("batch", None), self._page_idx.shape))
            self.paged_meta = self._tune_paged_kernel(retune)
        self._seed = seed
        # Sampled tokens stay ON DEVICE between steps: the previous step's
        # samples feed the next step's decode rows (token_src) and every
        # committed sample lands in a per-slot output buffer; the host
        # reads a row only when its request finishes.  Without EOS
        # detection the whole run is free of per-step device syncs, so
        # host scheduling overlaps device compute exactly like the static
        # engine's chained decode loop.  Cache / buffers are donated
        # (in-place updates); slot resets run as their own jitted pass
        # only on admission steps.
        #
        # A step executes as one batched (n_slots, 1) decode plus one
        # single-row (1, prefill_chunk) forward per prefilling slot
        # (cache_row / set_cache_row) — so prefill work scales with the
        # chunk's own tokens, never with n_slots x chunk.
        # mesh-aware jits: every step function traces under the engine's
        # sharding context (activating the model's logical-axis
        # constraints and, with sp_kv, the SP-KV decode path) and pins
        # its donated outputs to the NamedSharding layout so buffers are
        # actually reused in place across steps
        triple_sh = (self._slot_sharding, self._cache_sharding,
                     self._out_sharding)
        if self.spec_decode:
            # the speculative step returns two extra per-row arrays (the
            # accepted count and the accepted token values) that the
            # host reads back every step to feed the drafter
            self._decode_fn = self._jit(
                self._make_spec_decode_fn(),
                donate_argnums=(1, 2, 3), static_argnums=(12,),
                out_shardings=triple_sh + (self._slot_sharding,
                                           self._spec_tok_sharding))
            # no-draft fast path: a step where the drafter proposed
            # nothing would pay the (1 + spec_k)-wide verify forward to
            # commit one token per row — dispatch the plain single-token
            # program instead (the exact spec-off program, so such steps
            # cost what a non-speculative engine pays)
            self._plain_decode_fn = self._jit(self._make_decode_fn(),
                                              donate_argnums=(1, 2, 3),
                                              static_argnums=(12,),
                                              out_shardings=triple_sh)
        else:
            self._decode_fn = self._jit(self._make_decode_fn(),
                                        donate_argnums=(1, 2, 3),
                                        static_argnums=(12,),
                                        out_shardings=triple_sh)
        self._prefill_fn = self._jit(self._make_prefill_fn(),
                                     donate_argnums=(1, 2, 3),
                                     static_argnums=(12,),
                                     out_shardings=triple_sh)
        self._reset_fn = self._jit(model.reset_cache_slots,
                                   donate_argnums=(0,),
                                   out_shardings=self._cache_sharding)
        # admission-time context install (vlm/audio cross K/V); compiled
        # once — extra shapes are fixed by the config
        self._install_fn = self._jit(model.install_slot_context,
                                     donate_argnums=(1,),
                                     out_shardings=self._cache_sharding)
        # prefix-hit admission: copy the donor slot's first n tokens of
        # K/V into the admitted slot (traced src/dst/n -> compiled once)
        self._prefix_fn = self._jit(model.install_cache_prefix,
                                    donate_argnums=(0,),
                                    out_shardings=self._cache_sharding)
        # output rows outnumber slots so finished requests' tokens can
        # stay on device until a flush point — the host reads the buffer
        # once per ~2*n_slots finishes instead of syncing every finish
        self._n_out_rows = 3 * n_slots
        self._out_buf = self._put_out(
            jnp.zeros((self._n_out_rows, max_len), jnp.int32))
        self._prev_sampled = self._put_slot(
            jnp.zeros((n_slots,), jnp.int32))
        self._free_rows = list(range(self._n_out_rows))
        self._slot_row = np.full((n_slots,), -1, np.int32)
        self._pending: List[Request] = []        # finished, tokens unread
        self._pending_rows: Dict[int, int] = {}  # rid -> out row
        self._step_idx = 0
        self._seen_discarded = 0
        self._cost = StepCostModel(model.cfg, max_len)
        self.stats = EngineStats()
        self._results: Dict[int, np.ndarray] = {}
        # last executed step's composition, for the open-loop frontend's
        # event records (set before commit so token counts are pre-commit;
        # None when the last iteration had no plan)
        self.last_plan: Optional[StepPlan] = None
        self.last_sampled_rids: List[tuple] = []   # [(slot, rid)]
        self.last_admitted_rids: List[int] = []    # rids first-scheduled
        # the public record of the last step that ran a plan
        # (serve/trace.py; None after an iteration that ran nothing)
        self.last_event: Optional[trace.StepEvent] = None
        # opt-in build-time trace lint: compile the decode/prefill step
        # fns ahead of the first request and run repro.analysis.trace's
        # rules (hot gathers, predication density, counter-blind scans,
        # f32 upcasts, host callbacks, missed donation) over the jaxpr +
        # HLO.  The result rides in ``analysis_meta`` so serve_bench can
        # record it next to the measured numbers.  Imported lazily:
        # analyze=False engines never touch the analysis subsystem.
        self.analysis_meta: Optional[Dict[str, Any]] = None
        if analyze:
            from repro.analysis.trace import analyze_serve_engine
            self.analysis_meta = analyze_serve_engine(self)

    # -- mesh layout ------------------------------------------------------
    def _init_mesh_layout(self) -> None:
        """Resolve the slot-shard count and the ``NamedSharding`` layout
        of every donated buffer over ``self.mesh``, and lay the
        parameters out; forced-replication decisions recorded by the
        resolver land in ``sharding_meta`` (satellite of the roofline
        report)."""
        model, mesh, rules = self.model, self.mesh, self.rules
        extra_decisions: List[str] = []
        if self.sp_kv:
            # honesty over intent: sp_kv only *runs* when the kv_seq rule
            # resolves to axes this mesh actually has (the family has a
            # KV cache at all) AND their size divides the cache length —
            # attn_decode picks the shard_map path on rule *presence*, so
            # an unexecutable rule must be stripped, not just replicated
            # by the resolver.  Record what executes, not the ask.
            kv_rule = rules.get("kv_seq")
            kv_axes = tuple(a for a in (kv_rule if isinstance(kv_rule, tuple)
                                        else (kv_rule,) if kv_rule else ())
                            if a in mesh.shape)
            size = (math.prod(mesh.shape[a] for a in kv_axes)
                    if kv_axes else 0)
            if not kv_axes or self.max_len % size:
                self.sp_kv = False
                self.rules = rules = dict(rules, kv_seq=None)
                if kv_axes:
                    extra_decisions.append(
                        f"sp_kv disabled: cache length {self.max_len} not "
                        f"divisible by mesh axes {kv_axes} (size {size})")
        with paxes.sharding_ctx(mesh, rules):
            spec = paxes.resolve_spec(("batch",), (self.n_slots,))
            ax = spec[0] if len(spec) else None
            axs = (ax,) if isinstance(ax, str) else (ax or ())
            self.n_shards = math.prod(mesh.shape[a] for a in axs) if axs else 1
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(self.n_slots, self.max_len))
            self._cache_sharding = paxes.tree_shardings(
                model.cache_specs(), cache_sds, mesh, rules)
            self._slot_sharding = paxes.named_sharding(
                ("batch",), (self.n_slots,))
            self._out_sharding = paxes.named_sharding(
                ("batch", None), (3 * self.n_slots, self.max_len))
            self._spec_tok_sharding = paxes.named_sharding(
                ("batch", None), (self.n_slots, self.spec_k + 1))
            params_sds = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.params)
            pspecs = model.param_specs()
            try:
                param_sh = paxes.tree_shardings(pspecs, params_sds,
                                                mesh, rules)
            except (KeyError, TypeError, ValueError):
                # re-laid-out params (e.g. weight-only int8): derive the
                # quantized spec tree the way the dry-run does
                from repro.models.quant import quantize_specs
                param_sh = paxes.tree_shardings(
                    quantize_specs(pspecs, params_sds), params_sds,
                    mesh, rules)
            self.params = jax.device_put(self.params, param_sh)
            decisions = extra_decisions + paxes.decisions()
        self.sharding_meta = layout_report(mesh, rules, decisions,
                                           n_shards=self.n_shards,
                                           sp_kv=self.sp_kv)

    def _jit(self, fn, *, out_shardings=None, **kw):
        """``jax.jit`` that, when a mesh is configured, pins output
        shardings and runs every (trace-triggering) call inside the
        engine's sharding context."""
        if self.mesh is None:
            return jax.jit(fn, **kw)
        jfn = jax.jit(fn, out_shardings=out_shardings, **kw)
        mesh, rules = self.mesh, self.rules

        def call(*args):
            with paxes.sharding_ctx(mesh, rules):
                return jfn(*args)

        return call

    def _put_slot(self, x):
        return x if self.mesh is None else jax.device_put(
            x, self._slot_sharding)

    def _put_out(self, x):
        return x if self.mesh is None else jax.device_put(
            x, self._out_sharding)

    def _sample(self, last, temperatures, step_idx, salt, any_temp):
        """last: (R, V) logits; returns (R,) int32 tokens (shared
        implementation: serve/sampling.py)."""
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(self._seed), salt), step_idx)
        return sampling.sample_tokens(last, temperatures, key,
                                      any_temp=any_temp)

    def _tune_paged_kernel(self, retune: bool) -> Dict[str, Any]:
        """Pick ``block_pages`` for the paged kernel via the persistent
        ``core.autotune`` sweep cache (measured_sweep interleaved
        medians; ``retune=True`` forces re-measurement)."""
        cfg = self.model.cfg
        if cfg.family == "ssm":
            # no attention KV on the decode path: the paged context only
            # swaps the embedding lookup; nothing to tune
            return {"skipped": "family 'ssm' has no attention KV cache"}
        from repro.core import autotune
        info = autotune.tune_paged_attention(
            n_slots=self.n_slots, max_len=self.max_len,
            page_size=self.kv.page_size, n_kv_heads=cfg.n_kv_heads,
            n_q_heads=cfg.n_heads, head_dim=cfg.resolved_head_dim,
            dtype=cfg.compute_dtype,
            max_q_len=max(self.sched.prefill_chunk, self.spec_k + 1),
            retune=retune)
        self._paged_block_pages = int(info["block_pages"])
        return info

    def _paged_ctx(self, page_idx):
        from repro.models import attention
        return attention.paged_decode(attention.PagedDecodeState(
            page_idx=page_idx, page_size=self.kv.page_size,
            block_pages=self._paged_block_pages))

    def _make_decode_fn(self):
        model = self.model
        n_slots = self.n_slots
        if not self.paged_kernel:
            def decode_step(params, cache, out_buf, prev_sampled, tokens,
                            token_src, positions, n_valid, temperatures,
                            out_rows, out_idx, step_idx, any_temp):
                # decode rows take their input token from the previous
                # step's on-device samples
                tokens = tokens.at[:, 0].set(
                    jnp.where(token_src, prev_sampled, tokens[:, 0]))
                logits, cache, _ = model.forward(
                    params, tokens, positions, mode="decode", cache=cache,
                    n_valid=n_valid)
                nxt = self._sample(logits[:, 0], temperatures, step_idx, 0,
                                   any_temp)
                # commit: sample rows write their token (to the slot's
                # output row) and carry it forward; other rows keep their
                # previous sample (out-of-range column drops)
                out_buf = out_buf.at[out_rows, out_idx].set(nxt, mode="drop")
                is_sample = out_idx < out_buf.shape[1]
                prev_sampled = jnp.where(is_sample, nxt, prev_sampled)
                return prev_sampled, cache, out_buf

            return decode_step

        # paged variant: identical step, but the forward runs under the
        # paged-decode context (gather-free embedding + fused paged
        # attention) with the page-index device array as a real argument
        def decode_step(params, cache, out_buf, prev_sampled, tokens,
                        token_src, positions, n_valid, temperatures,
                        out_rows, out_idx, step_idx, any_temp, page_idx):
            tokens = tokens.at[:, 0].set(
                jnp.where(token_src, prev_sampled, tokens[:, 0]))
            with self._paged_ctx(page_idx):
                logits, cache, _ = model.forward(
                    params, tokens, positions, mode="decode", cache=cache,
                    n_valid=n_valid)
            nxt = self._sample(logits[:, 0], temperatures, step_idx, 0,
                               any_temp)
            out_buf = out_buf.at[out_rows, out_idx].set(nxt, mode="drop")
            is_sample = out_idx < out_buf.shape[1]
            prev_sampled = jnp.where(is_sample, nxt, prev_sampled)
            return prev_sampled, cache, out_buf

        return decode_step

    def _make_spec_decode_fn(self):
        """Draft-verify decode step (spec_decode=True): one forward over
        (n_slots, spec_k + 1) columns scores every fed token, greedy
        acceptance keeps the longest draft prefix matching the argmax
        chain plus the bonus token at the frontier, and the ragged-write
        contract commits per-row variable token counts in place.

        Same signature/donation as the plain step, plus two extra
        outputs: ``n_accept`` (n_slots,) and the accepted token values
        ``acc`` (n_slots, spec_k + 1) — the host readback that feeds the
        drafter and the scheduler's variable commit.  Everything on the
        device side stays gather-free (one-hot/iota selects, ``.at[]``
        scatters), matching the pinned ``serve.decode_step.spec``
        fingerprint.
        """
        model = self.model
        S = self.spec_k + 1
        paged = self.paged_kernel
        # token-addressable families (dense/moe/vlm/audio) commit in
        # place: the verify pass's ragged write already stored every fed
        # token's KV, so acceptance only rewinds the position counters
        # to the accepted frontier.  Recurrent families (ssm/hybrid)
        # advance scan state per step, which cannot be rewound — they
        # replay the sweep with n_valid = n_accept against the pre-step
        # state instead (two passes over the same step's inputs; the
        # masked recurrence commits exactly the accepted prefix).
        two_pass = not model.decode_state.token_addressable

        def spec_decode_step(params, cache, out_buf, prev_sampled, tokens,
                             token_src, positions, n_valid, temperatures,
                             out_rows, out_idx, step_idx, any_temp,
                             page_idx=None):
            tokens = tokens.at[:, 0].set(
                jnp.where(token_src, prev_sampled, tokens[:, 0]))

            def forward(c, nv):
                if paged:
                    with self._paged_ctx(page_idx):
                        return model.forward(params, tokens, positions,
                                             mode="decode", cache=c,
                                             n_valid=nv)
                return model.forward(params, tokens, positions,
                                     mode="decode", cache=c, n_valid=nv)

            logits, new_cache, _ = forward(cache, n_valid)
            # verify: column i's argmax is the model's next token after
            # consuming fed tokens 0..i.  Column 0 goes through the
            # engine's sampler (same key/salt as the plain step, so the
            # first committed token is sample-for-sample identical);
            # temp>0 rows never carry drafts, so columns 1.. are greedy
            # by construction.
            a = jnp.argmax(logits, axis=-1).astype(jnp.int32)      # (n, S)
            nxt0 = self._sample(logits[:, 0], temperatures, step_idx, 0,
                                any_temp)
            acc = a.at[:, 0].set(nxt0)
            cols = jnp.arange(S, dtype=jnp.int32)[None, :]
            # draft token i+1 is accepted iff it was actually fed and
            # equals committed token i; acceptance = longest matching
            # prefix + the bonus token at the frontier
            match = ((acc[:, :-1] == tokens[:, 1:])
                     & (cols[:, :-1] + 1 < n_valid[:, None]))
            n_match = jnp.cumprod(match.astype(jnp.int32),
                                  axis=1).sum(axis=1)
            n_accept = jnp.where(n_valid > 0, n_match + 1, 0)      # (n,)
            if two_pass:
                _, new_cache, _ = forward(
                    cache, n_accept.astype(n_valid.dtype))
            else:
                # stale KV past the rewound counter is invisible under
                # the kv_valid mask and overwritten by the next step
                new_cache = model.adjust_cache_counters(
                    new_cache, n_valid - n_accept)
            # bonus token at the acceptance frontier chains into the
            # next step's decode input (one-hot sum, not a gather)
            sel = cols == jnp.maximum(n_accept - 1, 0)[:, None]
            bonus = jnp.where(sel, acc, 0).sum(axis=1).astype(jnp.int32)
            is_sample = out_idx < out_buf.shape[1]
            prev_sampled = jnp.where(is_sample, bonus, prev_sampled)
            # scatter the accepted tokens into the slot's output row
            # (out-of-range columns drop, exactly like the plain step)
            wcols = jnp.where(cols < n_accept[:, None],
                              out_idx[:, None] + cols, out_buf.shape[1])
            out_buf = out_buf.at[out_rows[:, None], wcols].set(
                acc, mode="drop")
            return prev_sampled, new_cache, out_buf, n_accept, acc

        return spec_decode_step

    def _make_prefill_fn(self):
        model = self.model
        paged = self.paged_kernel

        def prefill_row(params, cache, out_buf, prev_sampled, slot,
                        tokens, positions, n_valid, temperature, out_row,
                        out_idx, step_idx, any_temp):
            row = model.cache_row(cache, slot)
            if paged:
                # batch-1 row: page_idx=None -> row-local identity map
                with self._paged_ctx(None):
                    logits, row, _ = model.forward(
                        params, tokens, positions, mode="decode", cache=row,
                        n_valid=n_valid)
            else:
                logits, row, _ = model.forward(
                    params, tokens, positions, mode="decode", cache=row,
                    n_valid=n_valid)
            cache = model.set_cache_row(cache, slot, row)
            # the sample comes from the last valid column (only commits —
            # via out_idx — when the chunk completes the prompt)
            last_col = jnp.maximum(n_valid - 1, 0)
            last = jnp.take_along_axis(
                logits, last_col[:, None, None], axis=1)[:, 0]   # (1, V)
            # salt by slot so prefills finishing in the same step draw
            # independent noise (decode rows share one batched draw)
            nxt = self._sample(last, temperature[None], step_idx, 1 + slot,
                               any_temp)[0]
            out_buf = out_buf.at[out_row, out_idx].set(nxt, mode="drop")
            prev_sampled = prev_sampled.at[slot].set(
                jnp.where(out_idx < out_buf.shape[1], nxt,
                          prev_sampled[slot]))
            return prev_sampled, cache, out_buf

        return prefill_row

    # -- API ------------------------------------------------------------
    def reset(self) -> None:
        """Clear all serving state (queue, slots, cache, stats, results)
        but keep the compiled step functions — e.g. to re-run a workload
        without paying compilation again."""
        self.kv = PagedKVCache(self.n_slots, self.max_len,
                               self.kv.page_size,
                               page_budget=self.kv.page_budget,
                               slot_aux_tokens=self.kv.slot_aux_tokens,
                               prefix_pool=self.kv.prefix_pool,
                               n_shards=self.n_shards)
        self.sched = Scheduler(self.kv,
                               prefill_chunk=self.sched.prefill_chunk,
                               eos_id=self.sched.eos_id,
                               chunk_policy=self.sched.chunk_policy,
                               tbt_target_s=self.sched.tbt_target_s,
                               spec_k=self.spec_k)
        if self.drafter is not None:
            from repro.serve.draft import NGramDrafter
            self.drafter = NGramDrafter(self.spec_k,
                                        **self._drafter_throttle())
            self._draft_stale = set()
        if self.check:
            from repro.analysis.schedcheck import SchedChecker
            self.checker = SchedChecker.attach(self.kv, self.sched)
        self.cache = self.model.init_cache(self.n_slots, self.max_len)
        if self.mesh is not None:
            self.cache = jax.device_put(self.cache, self._cache_sharding)
        self._out_buf = self._put_out(
            jnp.zeros((self._n_out_rows, self.max_len), jnp.int32))
        self._prev_sampled = self._put_slot(
            jnp.zeros((self.n_slots,), jnp.int32))
        self._free_rows = list(range(self._n_out_rows))
        self._slot_row = np.full((self.n_slots,), -1, np.int32)
        self._pending = []
        self._pending_rows = {}
        self._step_idx = 0
        self._seen_discarded = 0
        self.stats = EngineStats()
        self._results = {}
        self._no_step()

    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               temperature: float = 0.0,
               extra: Optional[Dict[str, Any]] = None) -> int:
        """Queue a request.  ``extra`` carries the request's read-only
        context — (T, d) or (1, T, d) arrays, e.g. ``image_embeds`` /
        ``audio_frames`` — required for the cross-context families."""
        need = self.model.decode_state.requires_extra
        missing = [k for k in need if extra is None or k not in extra]
        if missing:
            raise ValueError(
                f"family {self.model.cfg.family!r} requires extra "
                f"context {missing} at submit()")
        unknown = [k for k in (extra or {}) if k not in need]
        if unknown:
            # a stray key would otherwise trigger a no-op full-cache
            # install round-trip at every (re-)admission — and hide typos
            raise ValueError(
                f"family {self.model.cfg.family!r} takes no extra "
                f"context {unknown}; it requires exactly {list(need)}")
        if extra is not None:
            # normalize to batch-1 host arrays so every install call
            # shares one compiled shape (shape rule shared with the
            # adapters' install path)
            extra = {k: decode_state.ensure_request_context(np.asarray(v))
                     for k, v in extra.items()}
        req = self.sched.submit(np.asarray(prompt), max_new_tokens,
                                temperature=temperature, extra=extra,
                                step=self._step_idx)
        if self.drafter is not None:
            self.drafter.add_request(req.rid, req.prompt)
        return req.rid

    def _drafter_throttle(self) -> Dict[int, object]:
        """Family-aware throttle parameters for the n-gram drafter.

        Recurrent families (ssm/hybrid) verify drafts with the two-pass
        masked recurrence, so a rejected draft costs roughly twice what
        it does on a token-addressable family — their break-even
        acceptance is higher and mispredicted probes hurt more, so they
        get a higher floor and a sparser probe cadence."""
        if self.model.decode_state.token_addressable:
            return {}
        return dict(accept_floor=0.6, probe_every=32, min_trials=2)

    def _propose_drafts(self) -> Dict[int, np.ndarray]:
        """Host-side draft pass: ask the n-gram drafter for continuation
        proposals for every temp-0 decoding slot (speculation is a
        greedy-acceptance scheme, so sampled rows never carry drafts).

        The adaptive throttle gates first — a throttled request costs
        nothing here (no history resync, no suffix search) and, once
        every row is quiet, the whole step takes the no-draft fast path.
        Histories left stale by fast-path steps (which skip the per-step
        host readback) are resynced lazily from ``out_buf`` only for the
        requests that actually get to propose."""
        from repro.serve.scheduler import RequestState
        drafts: Dict[int, np.ndarray] = {}
        for slot, req in self.sched.active.items():
            if (req.state is RequestState.DECODING
                    and req.temperature == 0):
                if self.drafter.throttled(req.rid, self._step_idx):
                    continue
                if req.rid in self._draft_stale:
                    row = int(self._slot_row[slot])
                    toks = np.asarray(
                        self._out_buf[row, :req.n_generated])
                    self.drafter.commit(req.rid, req.n_generated, toks)
                    self._draft_stale.discard(req.rid)
                d = self.drafter.propose(req.rid)
                if len(d):
                    drafts[slot] = d
        return drafts

    def _spec_accepted(self, plan: StepPlan, n_acc_dev,
                       acc_dev) -> Dict[int, np.ndarray]:
        """Read back this step's accepted tokens per sampled slot (the
        speculative path's one per-step host sync — the drafter needs
        the values).  Decode rows take their accepted prefix from the
        verify outputs; prefill-completing rows sampled exactly one
        token, which lives in ``prev_sampled``.  A no-draft fast-path
        step ran the plain program (``n_acc_dev is None``): every
        sampled row took exactly one token, all from ``prev_sampled``."""
        accepted: Dict[int, np.ndarray] = {}
        n_acc = acc = prev_host = None
        for slot in plan.sample_slots:
            if plan.token_src[slot] and n_acc_dev is not None:
                if n_acc is None:
                    n_acc = np.asarray(n_acc_dev)
                    acc = np.asarray(acc_dev)
                accepted[slot] = acc[slot, :max(1, int(n_acc[slot]))].copy()
            else:
                if prev_host is None:
                    prev_host = np.asarray(self._prev_sampled)
                accepted[slot] = prev_host[slot:slot + 1].copy()
        return accepted

    def _spec_feedback(self, plan: StepPlan,
                       accepted: Dict[int, np.ndarray],
                       row_reqs: Dict[int, Request]) -> None:
        """Post-commit speculative bookkeeping: mirror committed tokens
        into the drafter (drop finished requests) and accumulate the
        draft/accept counters behind ``EngineStats.accept_rate``."""
        drafted = accepted_draft = 0
        for slot in plan.sample_slots:
            req = row_reqs[slot]
            if plan.token_src[slot]:
                d = int(plan.n_valid[slot]) - 1
                a = self.sched.last_commit_counts[slot] - 1
                drafted += d
                accepted_draft += a
                # acceptance feedback drives the drafter's adaptive
                # throttle (quiet down requests whose drafts keep
                # getting rejected)
                self.drafter.feedback(req.rid, d, a)
            if req.finish_reason:
                self.drafter.drop(req.rid)
                self._draft_stale.discard(req.rid)
            elif req.rid in self._draft_stale:
                # history already misses fast-path tokens — appending
                # this commit would leave a gap; the rid stays stale and
                # resyncs in full from out_buf when it next proposes
                pass
            else:
                self.drafter.commit(req.rid, req.n_generated,
                                    accepted[slot])
        self.stats.drafted_tokens += drafted
        self.stats.accepted_draft_tokens += accepted_draft

    def step(self) -> bool:
        """Run one engine iteration; False when no work remains.

        Each phase runs inside its profiler span (serve/trace.py), and a
        step that ran a plan leaves its ``StepEvent`` in ``last_event``."""
        if not self.sched.has_work():
            self._no_step()
            return False
        with StepTraceAnnotation(trace.SERVE_STEP) as span:
            with TraceAnnotation(trace.PLAN):
                plan = (self.sched.next_plan(self._step_idx,
                                             drafts=self._propose_drafts())
                        if self.spec_decode
                        else self.sched.next_plan(self._step_idx))
            if plan is None:
                self._no_step()
                return self.sched.has_work()
            span.set_metadata(step_num=self._step_idx)
            t0 = now()
            if plan.reset_mask.any():
                with TraceAnnotation(trace.ADMIT):
                    self._admit_slots(plan)
            step_idx = np.int32(self._step_idx)
            n_acc_dev = acc_dev = None
            if plan.n_decode:
                with TraceAnnotation(trace.DECODE):
                    n_acc_dev, acc_dev = self._dispatch_decode(plan, step_idx)
            for pf in plan.prefills:
                with TraceAnnotation(trace.PREFILL):
                    (self._prev_sampled, self.cache,
                     self._out_buf) = self._prefill_fn(
                        self.params, self.cache, self._out_buf,
                        self._prev_sampled, np.int32(pf.slot), pf.tokens,
                        pf.positions, pf.n_valid, np.float32(pf.temperature),
                        np.int32(self._slot_row[pf.slot]),
                        np.int32(pf.out_idx), step_idx, pf.temperature > 0)
            with TraceAnnotation(trace.COMMIT):
                self._commit(plan, n_acc_dev, acc_dev, t0)
            if self.checker is not None:
                self.checker.check_step()
            return self.sched.has_work()

    def _no_step(self) -> None:
        """Records of an iteration that ran nothing."""
        self.last_plan = None
        self.last_event = None
        self.last_sampled_rids = []
        self.last_admitted_rids = []

    def _admit_slots(self, plan: StepPlan) -> None:
        """Give every slot entering this step a fresh output row and a
        clean cache row (reset, prefix copy, context install)."""
        for slot in np.nonzero(plan.reset_mask)[0]:
            # a request enters this slot: give it a fresh output row.  A
            # still-mapped old row can only be a preemption orphan —
            # finished requests hand their row to _pending_rows at commit
            # (slot_row reset to -1) — so recycle it unconditionally.
            old = int(self._slot_row[slot])
            if old >= 0:
                self._free_rows.append(old)
            if not self._free_rows:
                self._flush_results()
            self._slot_row[slot] = self._free_rows.pop()
        # three-phase (re-)admission: zero the cold slots, then copy
        # cached prefixes from their donor rows (prefix-hit slots are
        # NOT zeroed first — the copy overwrites/zeros every token-
        # addressable leaf itself, and a donor may be the same slot),
        # then install per-request read-only context.  The scheduler
        # guarantees no donor row is claimed by this same plan, so
        # zeroing before copying can never destroy a donor.
        zero_mask = plan.reset_mask.copy()
        prefix_installs = []
        for slot in np.nonzero(plan.reset_mask)[0]:
            req = self.sched.active.get(int(slot))
            if req is not None and req.prefix_len > 0:
                zero_mask[slot] = False
                prefix_installs.append((int(slot), int(req.prefix_src),
                                        int(req.prefix_len)))
        if zero_mask.any():
            self.cache = self._reset_fn(self.cache, zero_mask)
        for dst, src, n_tok in prefix_installs:
            self.cache = self._prefix_fn(self.cache, np.int32(src),
                                         np.int32(dst), np.int32(n_tok))
        for slot in np.nonzero(plan.reset_mask)[0]:
            # install the request's read-only context into the row
            # (cross K/V projection; the audio adapter also runs the
            # encoder here, once) — after any prefix copy, so the
            # context always reflects THIS request
            req = self.sched.active.get(int(slot))
            if req is not None and req.extra:
                self.cache = self._install_fn(
                    self.params, self.cache, np.int32(slot), req.extra)

    def _dispatch_decode(self, plan: StepPlan, step_idx):
        """Dispatch the batched decode program; returns the speculative
        verify outputs ``(n_accept, accepted)`` on device, or Nones."""
        any_temp = bool((plan.temperatures > 0).any())
        decode_args = (
            self.params, self.cache, self._out_buf, self._prev_sampled,
            plan.tokens, plan.token_src, plan.positions, plan.n_valid,
            plan.temperatures, self._slot_row.copy(), plan.out_idx,
            step_idx, any_temp)
        if self.paged_kernel:
            decode_args = decode_args + (self._page_idx,)
        if self.spec_decode and not (plan.n_valid > 1).any():
            # no drafts in flight this step: run the plain
            # single-token program (byte-identical to the spec-off
            # step) instead of the wide verify forward
            plain_args = (decode_args[:4]
                          + (plan.tokens[:, :1], plan.token_src,
                             plan.positions[:, :1])
                          + decode_args[7:])
            (self._prev_sampled, self.cache,
             self._out_buf) = self._plain_decode_fn(*plain_args)
        elif self.spec_decode:
            (self._prev_sampled, self.cache, self._out_buf,
             n_acc_dev, acc_dev) = self._decode_fn(*decode_args)
            return n_acc_dev, acc_dev
        else:
            (self._prev_sampled, self.cache,
             self._out_buf) = self._decode_fn(*decode_args)
        return None, None

    def _commit(self, plan: StepPlan, n_acc_dev, acc_dev, t0: float) -> None:
        """Commit the step's samples, feed back its wall, count it, and
        record its ``StepEvent``."""
        # frontend event capture: which requests sampled a token this
        # step and which were first scheduled (admitted into a reset
        # slot), recorded pre-commit while the slot -> rid map is live.
        # A slot admitted and then preempted while composing this same
        # plan is in reset_mask but no longer active — skip it.
        self.last_plan = plan
        self.last_sampled_rids = [
            (slot, self.sched.active[slot].rid)
            for slot in plan.sample_slots if slot in self.sched.active]
        self.last_admitted_rids = [
            self.sched.active[int(s)].rid
            for s in np.nonzero(plan.reset_mask)[0]
            if int(s) in self.sched.active]
        # EOS detection is the only per-step host sync; count-based
        # finishing leaves the device queue free-running.  A speculative
        # *verify* step syncs (the drafter needs the committed token
        # values), but a no-draft fast-path step commits exactly one
        # token per row like a plain step — the drafter's histories are
        # just marked stale and lazily resynced from ``out_buf`` at the
        # next proposal, so draft-less stretches keep the device queue
        # free-running too.
        sampled = (np.asarray(self._prev_sampled)
                   if self.sched.eos_id is not None else None)
        if self.spec_decode:
            row_reqs = {slot: self.sched.active[slot]
                        for slot in plan.sample_slots}
            if n_acc_dev is None:
                # fast-path / prefill-only step: one token per sampled
                # row, commit by count exactly like the plain engine
                done = self.sched.commit(plan, sampled, self._step_idx)
                for slot in plan.sample_slots:
                    req = row_reqs[slot]
                    if req.finish_reason:
                        self.drafter.drop(req.rid)
                        self._draft_stale.discard(req.rid)
                    else:
                        self._draft_stale.add(req.rid)
            else:
                accepted = self._spec_accepted(plan, n_acc_dev, acc_dev)
                done = self.sched.commit(plan, sampled, self._step_idx,
                                         accepted=accepted)
                self._spec_feedback(plan, accepted, row_reqs)
        else:
            done = self.sched.commit(plan, sampled, self._step_idx)
        for req in done:
            # tokens stay on device; materialized at the next flush point.
            # Row ownership moves from the slot to the pending map so the
            # slot's next admission cannot free or alias it.
            self._pending.append(req)
            self._pending_rows[req.rid] = int(self._slot_row[req.finish_slot])
            self._slot_row[req.finish_slot] = -1
        dt = now() - t0
        if self.step_feedback == "wall":
            # feed the stall-free chunk policy's per-token estimate; the
            # frontend's model clock sets step_feedback="external" and
            # notes its deterministic modeled times instead
            self.sched.note_step_wall(
                dt, plan.n_decode + plan.n_prefill_tokens)
        self.stats.steps.append(StepRecord(
            wall_s=dt, n_decode=plan.n_decode,
            n_prefill_tokens=plan.n_prefill_tokens,
            occupancy=self.kv.occupancy(),
            page_utilization=self.kv.page_utilization()))
        # count only *useful* tokens: samples a preemption later throws
        # away (victim re-prefills from token 0) come back off the total
        discarded = self.sched.discarded_tokens - self._seen_discarded
        self._seen_discarded = self.sched.discarded_tokens
        counts = self.sched.last_commit_counts
        committed = (sum(counts.values())
                     if self.spec_decode else len(plan.sample_slots))
        self.stats.generated_tokens += committed - discarded
        self.stats.prefix_hit_tokens = self.sched.prefix_hit_tokens
        self.stats.wall_s += dt
        self.last_event = trace.StepEvent(
            step=self._step_idx, n_decode=plan.n_decode,
            decode_pos=tuple(plan.positions[plan.n_valid > 0, 0].tolist()),
            prefills=tuple((int(p.positions[0, 0]), int(p.n_valid[0]),
                            bool(p.completes_prompt))
                           for p in plan.prefills),
            sampled=tuple((rid, int(counts.get(slot, 1)))
                          for slot, rid in self.last_sampled_rids),
            admitted=tuple(self.last_admitted_rids),
            finished=tuple(req.rid for req in done),
            preempted=self.sched.take_preempted(),
            ready=self._out_buf)
        self._step_idx += 1

    def _flush_results(self) -> None:
        """Materialize finished requests' tokens (one buffer transfer)
        and recycle their output rows."""
        if not self._pending:
            return
        with TraceAnnotation(trace.FLUSH):
            buf = np.asarray(self._out_buf)
            for req in self._pending:
                row = self._pending_rows.pop(req.rid)
                toks = buf[row, :req.n_generated].copy()
                req.generated = toks.tolist()
                self._results[req.rid] = toks
                self._free_rows.append(row)
            self._pending = []

    def run(self, max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Drain the queue; returns {rid: generated tokens}."""
        n, stalled = 0, 0
        while True:
            before = self._step_idx
            if not self.step():
                break
            n += 1
            if max_steps is not None and n >= max_steps:
                break
            # a planless iteration with work remaining means nothing can
            # proceed; without external arrivals that's a dead scheduler
            # state (e.g. a page budget too small for a single request)
            stalled = stalled + 1 if self._step_idx == before else 0
            if stalled > self.n_slots + 2:
                raise RuntimeError(
                    "scheduler stalled: work queued but no step can run "
                    "(page budget too small for an in-flight request?)")
        self._flush_results()
        if self.checker is not None:
            self.checker.check_drain()
        return dict(self._results)

    def results(self) -> Dict[int, np.ndarray]:
        """Flush and return every finished request's tokens so far
        ({rid: np.ndarray}) without requiring a full drain — the
        open-loop frontend's read path (requests keep arriving, so
        ``run()``'s drain semantics never apply)."""
        self._flush_results()
        return dict(self._results)

    def modeled_step_time(self, n_decode: int,
                          n_prefill_tokens: int) -> float:
        """Analytic seconds for one step of this composition: the
        costmodel's FLOPs/bytes against the reference ceilings
        (max(compute, memory) — the roofline bound time).  This is the
        deterministic virtual clock the open-loop frontend advances by
        under ``clock="model"``; it is a *model* number, never a wall."""
        flops, bytes_ = self._cost.step_cost(n_decode, n_prefill_tokens)
        hw = costmodel.TPU_V5E
        return max(flops / hw.peak_flops_bf16, bytes_ / hw.hbm_bw)

    @property
    def check_findings(self) -> List[Any]:
        """Shadow-checker findings so far ([] when ``check=False``)."""
        return [] if self.checker is None else list(self.checker.findings)

    def requests(self) -> List[Request]:
        return list(self.sched.finished)

    # -- convenience: old-ServeEngine-shaped entry point -----------------
    def generate(self, prompt_tokens, n_steps: int, extra=None) -> jax.Array:
        """Submit a (B, S) same-length batch greedily and decode
        ``n_steps`` tokens each — the legacy fixed-batch calling
        convention, served by the continuous engine.  ``extra`` is the
        static engine's batched convention: (B, T, d) arrays, split into
        per-request rows here."""
        prompts = np.asarray(prompt_tokens)
        rids = [self.submit(
            p, n_steps,
            extra=(None if extra is None else
                   {k: np.asarray(v)[i] for k, v in extra.items()}))
            for i, p in enumerate(prompts)]
        results = self.run()
        return jnp.asarray(np.stack([results[r] for r in rids]))


# ---------------------------------------------------------------------------
# legacy fixed-batch baseline
# ---------------------------------------------------------------------------
class StaticBatchEngine:
    """Run-to-completion fixed-batch engine: one prefill + a decode loop.

    The pre-continuous-batching baseline, kept purely for correctness
    (per-family temperature-0 parity tests) and throughput comparison
    (benchmarks/serve_bench.py).  All five families serve through
    ``ContinuousBatchingEngine`` in production.
    """

    def __init__(self, model: LM, params, max_len: int, batch: int, *,
                 sample_temperature: float = 0.0):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.batch = batch
        self.prefill_fn = jax.jit(make_prefill_step(model))
        self.decode_fn = jax.jit(make_serve_step(
            model, sample_temperature=sample_temperature))
        # token accounting only: the static engine is timed externally,
        # so no per-step walls
        self.stats = EngineStats()

    def generate(self, prompt_tokens, n_steps: int, extra=None):
        B, S = prompt_tokens.shape
        assert B == self.batch
        cache = self.model.init_cache(B, self.max_len)
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        nxt, cache = self.prefill_fn(self.params, cache, prompt_tokens,
                                     positions, extra)
        out = [nxt]
        for t in range(n_steps - 1):
            pos = jnp.full((B, 1), S + t, jnp.int32)
            nxt, cache = self.decode_fn(self.params, cache, nxt[:, None],
                                        pos, extra)
            out.append(nxt)
        self.stats.generated_tokens += B * n_steps
        return jnp.stack(out, axis=1)                      # (B, n_steps)
