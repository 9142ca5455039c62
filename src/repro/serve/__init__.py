"""Serving subsystem: continuous batching over the DecodeState protocol.

``ContinuousBatchingEngine`` (serve/engine.py) drives **all five workload
families** — lm (dense/moe), ssm, hybrid, vlm, audio — through one
family-agnostic contract, the **DecodeState protocol**
(models/decode_state.py).  A family registers an adapter that lays out
its entire per-slot decode state as a single pytree (every leaf carries
a batch/"slot" axis located by an axis-name spec), and implements:

  * ``init`` / ``specs`` — allocate the slotted state and describe its
    axes;
  * ``state_row`` / ``set_state_row`` — extract/insert one slot as a
    batch-1 state (the paged cache's slot-indexed read/write; generic,
    spec-driven);
  * ``reset_state_slots`` — masked zeroing of recycled slots;
  * ``install_context`` — admission-time write of a request's read-only
    context (vlm image-embed / audio encoder-output cross K/V), re-run
    after every preemption re-admission;
  * the **row-masked ragged write** — inside the layers: attention
    drops cache scatters past ``n_valid`` (attn_decode) and Mamba-2
    commits conv-window/SSD-state updates only for steps inside
    ``n_valid`` (mamba2.mamba_forward), so a mixed prefill/decode step
    leaves idle, preempted, and finished rows' state untouched.

A new family therefore needs exactly: a ``DecodeStateAdapter`` subclass
registered in models/decode_state.py, and ``n_valid`` support in any
stateful layer it introduces.  The engine, scheduler (admission, chunked
prefill, youngest-first recompute-style preemption) and paged-slot
accounting (serve/cache.py, including per-slot aux pages for installed
context) never special-case a family.

**Prefix caching** (``ContinuousBatchingEngine(prefix_cache=True)``) is
keyed on the page table:

  * *hash scheme* — a sha256 rolling hash of prompt-token chunks,
    checkpointed at every ``page_size`` boundary and seeded with the
    request's read-only-context hash (``cache.context_key``), so a
    boundary key commits exactly the tokens (and image/audio context)
    whose K/V the matching pages hold;
  * *refcount lifecycle* — ``PageTable`` pages carry refcounts: a pooled
    prefix entry holds one ref, every request admitted against it shares
    the prefix pages (``incref``) instead of allocating, and release
    drops one ref — pages recycle at zero, double release fails loudly;
  * *LRU bound* — at most ``prefix_pool`` entries are retained; pooled
    pages are additionally reclaimed LRU-first the moment a real
    allocation (admission / decode growth) would otherwise fail, so the
    pool only ever uses spare budget;
  * *admission* — the scheduler matches the longest cached page-aligned
    prefix, starts prefill at the matched offset, and the engine copies
    the donor slot's K/V rows once (``copy_state_prefix``: token-range
    copy + position counters) instead of recomputing chunk-by-chunk.
    Preemption releases donate the victim's committed prefix back to the
    pool, turning recompute-style preemption into copy-style.  Families
    whose state is not token-addressable (ssm / hybrid recurrent state)
    declare ``prefix_cachable = False`` and run with the cache off.

**Paged flash-decode** (``ContinuousBatchingEngine(paged_kernel=True)``,
the default) fuses decode attention with the page walk
(kernels/paged_attention) instead of gathering K/V rows at the XLA
level.  The contract:

  * *identity page layout* — the device cache's pool view
    ``(n_slots * pages_per_slot, page_size, NKV, H)`` assigns slot
    ``s`` the pool pages ``s * pages_per_slot + j``;
    ``PagedKVCache.page_index_array()`` returns exactly that map.  The
    ``PageTable``'s logical page ids are budget/refcount bookkeeping
    only — they never relocate device rows, so the index array is a
    build-time constant the kernel prefetches, not per-step traffic;
  * *ragged mask semantics* — KV token ``t`` of row ``b`` is attended
    by query column ``c`` iff ``t <= positions[b, c]`` (causality) and
    ``t < kv_valid[b]`` (the ``n_valid`` ragged contract); rows with
    ``kv_valid == 0`` produce all-zero NaN-free outputs.  SP-KV decode
    reuses the same kernels' (m, l, acc) partials under the existing
    pmax/psum cross-shard combine;
  * *autotuning* — the ``block_pages`` tile knob is swept through
    ``core.autotune`` at engine build, with the winner persisted to
    ``benchmarks/results/autotune_cache.json`` (a schema-valid perf
    Report; ``serve_bench --retune`` forces re-measurement) and the
    pick recorded in ``engine.paged_meta``;
  * ``paged_kernel=False`` restores the dense gather-then-attend
    decode bitwise — the temp-0 parity baseline
    (tests/test_kernels_paged.py pins token equality per family).

**Sharded serving** (``ContinuousBatchingEngine(mesh=...)``): the
decode slot ("batch") axis lays out over the production mesh's
``("pod", "data")`` axes and the whole subsystem partitions with it.
The sharding contract a family's adapter already satisfies by
construction:

  * *which leaves carry slot-axis specs* — every leaf of the adapter's
    state pytree names ``"batch"`` in its spec tuple; that same tuple
    is the leaf's sharding layout (``parallel.axes`` resolves it
    against the active rules, dropping non-divisible axes and recording
    the forced replication).  ``"kv_seq"`` leaves may additionally
    shard over ``"model"`` (``sp_kv=True`` — the flash-decoding
    combine in attention);
  * the generic row primitives (``state_row`` / ``set_state_row`` /
    ``reset_state_slots`` / ``copy_state_prefix``) address rows inside
    the sharded slot axis (GSPMD lowers the dynamic slices to the
    owning shard) and re-assert the resolved layout on every full-state
    output (``decode_state.constrain_state``) so donated buffers keep
    their ``NamedSharding`` across steps;
  * *what a shard-local scheduler guarantees* — slots split into
    contiguous shard blocks matching the device layout; each shard owns
    its own page-table budget and prefix pool; admission ranks shards
    by longest shard-local prefix match then free pages; a blocked
    growth preempts only within the stalled slot's shard; and a prefix
    donor is always in the admitted slot's shard, so the donor-row copy
    never crosses a device block.  A single-device engine (``mesh=None``)
    is bitwise unchanged.

A new family therefore gets sharded serving for free: correct spec
tuples are the entire contract.

**Open-loop front end** (serve/frontend.py + serve/arrivals.py +
serve/slo.py): the latency side of the measurement story.  The
contract:

  * *arrivals* — ``serve.arrivals`` generators emit seeded
    ``ArrivalRequest`` lists (Poisson, gamma with a burstiness knob,
    fixed-trace JSON replay under the ``repro.serve.trace`` schema, and
    a closed-loop compatibility generator with every arrival at t=0);
  * *intake* — ``OpenLoopFrontend`` runs a virtual-clock event loop:
    requests are submitted the moment the clock passes their arrival
    time (the scheduler hashes prefix keys at ``submit()``, so queued
    requests admit at their matched offset — enqueue-time prefix
    matching), ``engine.step()`` runs between arrivals, and the clock
    advances either by measured step walls (``clock="wall"``,
    timestamps exclusively via ``perf.measure.now()``) or by the
    costmodel's per-step bound time (``clock="model"``, fully
    deterministic — what the tests pin);
  * *telemetry* — per-request :class:`~repro.serve.slo.RequestEvents`
    (arrival, enqueue, first scheduled, every kept token, finish;
    preemption-discarded tokens are truncated out) reduce through
    ``slo.latency_summary`` to TTFT/TBT/E2E p50/p90/p99, queue depth
    over time, and goodput under a TTFT+TBT :class:`~repro.serve.slo.SLO`
    — the schema-validated ``latency`` Report block of
    ``serve_bench --open-loop``;
  * *stall-free chunking* — ``Scheduler(chunk_policy="stall_free",
    tbt_target_s=...)`` (exposed through the engine constructor) makes
    the prefill chunk a per-step decision: the width halves until the
    predicted step wall — from an EWMA per-token estimate fed by
    measured walls (or modeled times under the model clock) — fits the
    TBT target, so riding prefills never stall in-flight decodes.
    ``chunk_policy="fixed"`` (default) is the unchanged sarathi
    constant-chunk composition.

Closed-loop compatibility is structural: under
``arrivals.closed_loop_arrivals`` the frontend submits everything
before the first step, which is exactly ``engine.submit()``\\*N +
``engine.run()`` (temp-0 token parity pinned by
tests/test_serve_frontend.py).

**Shadow-state checking** (``ContinuousBatchingEngine(check=True)``):
the engine attaches the ``repro.analysis.schedcheck`` shadow state
machine to its page tables and scheduler — every alloc/incref/free,
admission, and preemption replays through a pure-Python twin that
validates refcount conservation, leak-free drains, slot/rid binding,
prefix-pool claims, and admission/preemption legality *before* the
real structure can raise (or silently corrupt).  Violations surface
as ``Finding`` records on ``engine.check_findings``; ``step()`` runs a
full conservation pass per step and ``run()`` a drain audit.  Cost is
host-side dict bookkeeping only (no jax), so the tier1 serve tests
run every engine with the checker on (tests/conftest.py).

**Speculative decoding** (``ContinuousBatchingEngine(spec_decode=True,
spec_k=k)``): the model-free n-gram drafter (``serve/draft.py``,
:class:`NGramDrafter`) proposes up to ``k`` continuation tokens per
greedy decode row from a prompt-lookup over the request's own history;
the engine's verify step scores all ``1 + k`` positions in one forward
through the same paged decode kernel.  The speculative contract:

  * **Acceptance rule** — greedy/temp-0 only: the accepted draft is the
    longest prefix of the proposal matching the verify pass's argmax at
    each position, plus the one model-sampled token that follows it
    (so every verify step commits 1..k+1 tokens per row and the token
    stream is *identical* to the non-speculative engine's; temperature
    rows never carry drafts).  Recurrent families (ssm/hybrid) verify
    through a two-pass masked recurrence — score wide, then re-advance
    the state by the accepted count.
  * **k-token commit** — acceptance feeds the scheduler's ``n_valid``
    ragged write: pages for the full fed width are grown *before* the
    step (a mid-step alloc after acceptance is a contract violation the
    scheduler raises on) and the unaccepted tail of the reserve is
    shrunk back at commit.
  * **TBT event semantics** — a multi-token step emits one event per
    committed token at the same step timestamp: time-between-tokens
    within a verify step is 0, the step wall lands on the gap to the
    row's *previous* step (``serve/slo.py``), and throughput metrics
    count committed tokens, not steps.
  * **Adaptive throttle** — per-request acceptance EMAs quiet the
    drafter when the model keeps rejecting (probing periodically), and
    draft-less steps dispatch the engine's plain single-token program,
    so incompressible workloads degrade to ~plain-engine cost instead
    of paying the wide verify for nothing.

``spec_decode=False`` (default) leaves the engine bit-for-bit the
non-speculative program (pinned by the ``serve.decode_step.*``
fingerprint baselines; parity by tests/test_serve_spec.py).

Remaining serve roadmap: per-shard intake queues feeding the admission
ranking, batched multi-row prefill chunks amortizing per-chunk
dispatch, a learned/draft-model drafter behind the NGramDrafter
interface, and an HTTP/streaming layer over the frontend.

**Tracing** (serve/trace.py): ``ContinuousBatchingEngine.step`` writes
``jax.profiler`` spans around each step (``serve_step``, with the step
index as ``step_num``) and its phases (plan, admit, decode, prefill,
commit; ``serve.flush`` around each result readback), always on and on
the profiler's clock; after each step that ran a plan,
``engine.last_event`` holds its ``StepEvent`` — rows run, tokens
committed per request, admissions, finishes, preemptions, and the device
array to wait on for the step's tokens.

``StaticBatchEngine`` remains the run-to-completion baseline used by the
per-family temperature-0 parity tests and benchmarks/serve_bench.py;
``serve/sampling.py`` holds the greedy/temperature sampling shared by
both engines.
"""
from repro.serve.arrivals import (  # noqa: F401
    ArrivalRequest,
    closed_loop_arrivals,
    gamma_arrivals,
    poisson_arrivals,
    save_trace,
    synthetic_requests,
    trace_arrivals,
    trace_payload,
)
from repro.serve.cache import (  # noqa: F401
    PagedKVCache,
    PageTable,
    PrefixEntry,
    context_key,
)
from repro.serve.draft import NGramDrafter  # noqa: F401
from repro.serve.engine import (  # noqa: F401
    ContinuousBatchingEngine,
    EngineStats,
    StaticBatchEngine,
    make_prefill_step,
    make_serve_step,
)
from repro.serve.frontend import (  # noqa: F401
    OpenLoopFrontend,
    OpenLoopResult,
)
from repro.serve.sampling import sample_tokens  # noqa: F401
from repro.serve.scheduler import (  # noqa: F401
    CHUNK_POLICIES,
    Request,
    RequestState,
    Scheduler,
    StepPlan,
)
from repro.serve.slo import (  # noqa: F401
    SLO,
    RequestEvents,
    latency_summary,
    queue_depth_stats,
)
from repro.serve.trace import StepEvent  # noqa: F401
