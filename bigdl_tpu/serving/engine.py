"""Continuous-batching decode engine over one paged KV pool.

The batch-at-a-time services (``GenerationService``'s micro-batcher,
≙ the reference's instance-queue in optim/PredictionService.scala) run
each batch TO COMPLETION: one long request strands the MXU and every
co-batched short request. This engine replaces request/response batch
dispatch with a persistent device-resident decode loop (the inference
analog of the RDMA paper's persistent dataflow, arxiv 1805.08430):

- ONE page pool of ``(max_pages, page_size, H_kv * D)`` leaves per
  layer (``serving.paging.PagePool``) lives on device for the engine's
  whole life and is the ONLY place KV is stored: running slots,
  prefills in flight and retained prefixes all hold refcounted pages
  of it through fixed-width block tables. Every compiled program's
  shape depends only on ``max_slots`` / ``prefill_rows`` /
  ``(max_pages, page_size)`` / the table width — never on load — so
  steady state runs a FIXED executable set (decode step, ragged
  prefill chunk, page copies, first-token sample) no matter what
  traffic does.
- a dedicated loop thread runs one fused ``decode_step_paged`` over
  ALL slots per iteration (rows at their own depths — the ragged
  per-row position vector path), so requests join and leave the batch
  at token granularity.
- admission happens MID-FLIGHT: a queued request reserves its whole
  page span up front (no mid-flight OOM; a pool that cannot cover it
  requeues the request at the head), then prefills in fixed chunks
  straight through its own table under a per-iteration token budget
  (``PrefillPolicy``) — each prefill round advances EVERY admission in
  flight by one chunk through one ragged ``prefill_rows``-wide dispatch
  (each row at its own offset), and a finished admission hands its
  table to its slot: nothing is copied. Decode never waits for more
  than one iteration's prefill budget.
- prompts are PREFIX-CACHED: a host-side radix trie
  (``paging.PagedPrefixIndex``) indexes retained pages by token-id
  prefix. An admission whose prompt shares a cached prefix SHARES the
  chunk-aligned pages (a refcount bump) and chunk-prefills only the
  novel tail — O(novel-suffix) TTFT instead of O(prompt). Finished
  slots share their pages back into the index under an LRU/ref-count
  policy; under allocation pressure unpinned entries are reclaimed, or
  demoted to a host tier when one is configured.
- rows finish at their OWN eos/token budget and their slot and pages
  free immediately for the next queued request.
- the engine optionally runs TENSOR-PARALLEL (``mesh=``): params are
  Megatron-sharded over the mesh's model axis
  (``parallel.tp.transformer_tp_rules`` / ``shard_params``), the page
  pools shard their KV-heads dimension along the same axis, and every
  compiled program above becomes ONE SPMD dispatch with jit-inserted
  collectives — models larger than one device's HBM serve at full
  interconnect bandwidth while the host-side control flow stays
  mesh-oblivious.
- decode is optionally SPECULATIVE (``draft=``): per iteration a
  cheaper draft model proposes ``spec_gamma`` tokens for ALL live
  slots in one ``lax.scan`` dispatch (its own page pool and tables,
  allocated/recycled in lockstep with the target's), the target
  scores every proposal through ONE ragged ``verify_chunk_paged``
  dispatch, and each row accepts a VARIABLE-length extension
  (1..gamma+1 tokens) into its slot — per-row position advance,
  per-row eos/budget truncation mid-extension, streaming handles
  emitting the burst in order. Compiled shapes depend only on
  ``(max_slots, spec_gamma)``, so the jit gauge stays flat.

Greedy output is token-identical to a lone ``model.generate`` call per
request — with the prefix cache COLD or WARM, and with speculation ON
or OFF (tested): shared pages hold bitwise the values prefill would
recompute (the reuse offset is chunk-aligned, so chunk geometry
matches; KV at position i depends only on tokens 0..i), same per-row
ragged decode step, same argmax tie-breaking; a draft only ever
changes HOW MANY target dispatches an output costs, never the output
(rejected proposals are replaced by the target's own argmax).
"""

from __future__ import annotations

import collections
import logging
import math
import sys
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.observability.costmodel import (
    DispatchCostModel, LoopPhaseAccumulator, device_peaks, program_cost,
)
from bigdl_tpu.observability.tracing import trace
from bigdl_tpu.observability.timeseries import (
    TimeSeriesSampler, render_dashboard,
)
from bigdl_tpu.serving.paging import (
    BlockTable, PagedPrefixIndex, PagePool, SnapshotStore, lane_leaves,
    page_leaves, with_lanes, with_pages,
)
from bigdl_tpu.serving.scheduler import (
    AdmissionQueue, PrefillPolicy, SpeculationPolicy, TokenBucket,
    page_fit_score, pages_needed,
)
from bigdl_tpu.serving.streams import (
    PRIORITY_RANK, EngineDraining, EngineStopped, RequestCancelled,
    RequestHandle, RequestRateLimited, RequestShed, RequestTimedOut,
)

#: finished-request timeline summaries kept for stats() percentiles and
#: /debug/requests "recent"
RECENT_TIMELINES = 256
#: finished usage records kept behind the ledger's top-N queries
USAGE_RECENT = 256
#: the idle loop's lost-wakeup safety net (submit/stop notify it)
IDLE_WAIT_S = 0.5
#: per-kind floor between two captured incident bundles
INCIDENT_COOLDOWN_S = 30.0
#: lane-state snapshots the store holds for each serving lane (a model
#: with lane state; derived, not a knob: the snapshot a lane resumed
#: from plus one its prefill takes, so a full engine still caches)
SNAPSHOTS_PER_LANE = 2


class _Admission:
    """Host-side progress of one chunked prefill. Up to
    ``prefill_rows`` of these are in flight at once, each owning one
    row of the prefill dispatch and one reserved slot; every prefill
    round advances all of them together through one ragged dispatch."""

    __slots__ = ("handle", "slot", "row", "ids", "t0", "base", "tail",
                 "n_chunks", "next_chunk", "d_ids",
                 "d_n_chunks", "d_next_chunk", "table", "d_table", "snaps",
                 "keep_at")

    def __init__(self, handle: RequestHandle, slot: int, row: int,
                 ids: np.ndarray, t0: int, base: int, n_chunks: int,
                 table: BlockTable, d_ids=None, d_n_chunks: int = 0,
                 d_table: Optional[BlockTable] = None):
        self.handle = handle
        self.slot = slot          # reserved slot (handoff target)
        self.row = row            # prefill-dispatch row this one owns
        self.ids = ids            # (n_chunks * chunk,) right-padded TAIL
        self.t0 = t0              # full prompt length
        self.base = base          # chunk-aligned cached-prefix offset
        self.tail = t0 - base     # tokens actually prefilled
        self.n_chunks = n_chunks
        self.next_chunk = 0
        #: speculative decoding: the DRAFT model prefills the FULL
        #: prompt through its own table (a prefix-cache hit skips
        #: target work only — the draft pool holds no reusable prefix),
        #: so its cursor can lag the target's on a hit; the admission
        #: completes when BOTH pools hold the prompt
        self.d_ids = d_ids        # (d_n_chunks * chunk,) full prompt
        self.d_n_chunks = d_n_chunks
        self.d_next_chunk = 0
        #: the BlockTables this admission writes through (full span
        #: reserved at admission; handed to the slot on completion,
        #: freed on abort)
        self.table: Optional[BlockTable] = table
        self.d_table: Optional[BlockTable] = d_table
        #: a model with lane state: the (position, snapshot id) pairs
        #: this request holds a reference on — the one it resumed from
        #: and those its prefill took; they follow it into its slot and
        #: its donated prefix entry. Of those it takes it keeps the
        #: newest and the one at ``keep_at``, the deepest stride
        #: boundary inside the pages its prompt MATCHED (where other
        #: prompts are seen to branch off); older ones go back
        self.snaps: List[tuple] = []
        self.keep_at = 0


class _SlotState:
    """Host-side view of one occupied KV slot."""

    __slots__ = ("handle", "pos", "last_token", "last_token_at",
                 "delivered", "snaps", "prompt_entry")

    def __init__(self, handle: RequestHandle, pos: int, last_token: int,
                 now: float):
        self.handle = handle
        #: cache position the NEXT decode step writes (= prompt length
        #: + delivered - 1: the last sampled token's KV is not yet
        #: cached, exactly generate()'s host-loop invariant — preserved
        #: under VARIABLE advance too: a speculative round delivering m
        #: tokens moves pos by m, and the slot's KV covers [0, pos)
        #: either way, which is what donation relies on)
        self.pos = pos
        self.last_token = last_token
        self.last_token_at = now
        self.delivered = 1
        self.snaps: List[tuple] = []
        #: the prefix entry donated when the prompt's prefill ended;
        #: the one donated when the slot is given up takes its place
        self.prompt_entry = None


class ContinuousBatchingEngine:
    """Token-granular continuous batching over ``TransformerLM``'s
    paged incremental-decoding API (``init_page_pool`` /
    ``prefill_chunk_at_paged`` / ``decode_step_paged``), with
    prefix-cached, batched multi-row prefill.

    ``submit()`` returns a ``RequestHandle`` immediately (bounded FCFS
    queue — ``QueueFull`` is the backpressure signal); the loop thread
    streams tokens into it as they decode. Sampling config is fixed per
    engine (it is part of the compiled program), exactly like
    ``GenerationService``; the default is greedy, whose output is
    token-identical to per-request ``model.generate``.

    KV PAGES: ``page_size`` tokens each (None derives
    ``gcd(prefill_chunk, 16)``; it must divide ``prefill_chunk`` so
    that a reused head ends on a page boundary and shared pages are
    never written), ``max_pages`` of them (None = every slot at full
    length plus an equal retained-prefix share). A request reserves
    ``ceil((prompt + max_new_tokens) / page_size)`` pages at admission
    and holds nothing longer than it needs.

    PREFIX CACHE: on by default. Retained prefixes are refcounted
    shares of the same pages, so the pool bounds their bytes;
    ``prefix_cache_rows`` caps how many entries the index keeps (None =
    two per slot; 0 disables the cache entirely — admission then always
    prefills the full prompt), and ``prefix_cache_bytes`` sets that cap
    in bytes at one full-length request per entry.
    ``prefix_host_rows`` / ``prefix_host_bytes`` (default 0) give
    reclaimed entries a host tier to demote to, budgeted at the same
    rate. ``prefix_min_tokens`` (default: one prefill chunk) is the
    floor under which a shared head is not worth retaining. Reuse is
    chunk-aligned, so matched lengths round down to a multiple of
    ``prefill_chunk``. ``admission_window > 1`` additionally lets the
    scheduler pop the queued request with the LONGEST cached prefix
    from the first ``admission_window`` candidates (FCFS on ties, with
    a hard starvation bound — see ``AdmissionQueue.pop_ready``).
    A request offers its pages to the index when it ends; with
    ``donate_at_prefill_end`` it offers its prompt's pages already
    when its prefill ends (the entry of its end takes that one's
    place), so a request for the same prefix that arrives while it
    still decodes is a hit. None asks the model
    (``model.donate_at_prefill_end``, off where it says nothing): a
    deployment's builder sets it where prompts are long and hot.

    BATCHED PREFILL: ``prefill_rows`` widens the prefill dispatch so
    that many queued admissions chunk-prefill TOGETHER through one
    ragged dispatch per round instead of one admission at a time.

    SPECULATIVE DECODING: pass ``draft=`` (a smaller ``TransformerLM``
    over the same vocabulary — ``nn.quantized.Quantizer.quantize(model)``
    builds the int8 clone PERF.md benchmarks) and each decode
    iteration becomes draft-propose/target-verify: the draft proposes
    ``spec_gamma`` tokens for ALL live slots in one ``lax.scan``
    dispatch (``_propose_fn_paged``), the target scores every proposal
    in one ragged ``verify_chunk_paged`` dispatch, and each row accepts its own
    1..gamma+1-token extension (matched proposals plus the target's
    correction/bonus token) — one target forward now yields several
    tokens wherever the draft agrees with the target. The draft owns a
    page pool and tables of its own, allocated and recycled in
    LOCKSTEP with the target's; admission chunk-prefills the draft's
    table alongside the target's (the FULL prompt — a prefix-cache hit
    skips target work only, so on hits the target's final chunk
    replays idempotently while the draft catches up). Greedy output
    stays token-identical to the non-speculative engine (and to lone
    ``model.generate``); with ``temperature > 0`` the engine runs full
    speculative SAMPLING (accept min(1, p/q), residual on rejection —
    Leviathan et al. 2023), distributed exactly as the target's
    tempered softmax, though not bitwise the non-speculative stream
    (the key schedule differs); ``top_k``/``top_p`` are rejected with
    a draft (the acceptance identity needs the unfiltered
    distributions). Compiled shapes depend only on
    ``(max_slots, spec_gamma)`` — the jit gauge stays flat after
    warmup with speculation on (tested). Acceptance telemetry:
    ``stats()["speculation"]``, ``bigdl_serving_spec_*`` instruments,
    and per-burst ``request/decode_token`` events carrying
    ``accepted=``.

    TENSOR-PARALLEL SERVING: pass ``mesh=`` (a ``jax.sharding.Mesh``
    with a ``model_axis`` axis — ``parallel.Engine.create_mesh([(
    "model", N)])``) and the whole engine runs SPMD: params load
    Megatron-sharded (``tp_rules``, default
    ``parallel.tp.transformer_tp_rules(model_axis)``), the page pools
    (target and draft) shard their KV-heads dimension along the model
    axis (the layout
    the column-parallel QKV writes with no collective;
    ``num_kv_heads`` must divide the axis size), host inputs enter
    replicated, and jit/GSPMD inserts the row-parallel all-reduces
    into the SAME compiled programs. Host-side control flow
    (scheduler, streams, ledger, recorder) is mesh-oblivious; greedy
    output stays token-identical to the unsharded engine (tested on a
    host-device CPU mesh), the jit gauge stays flat, and usage
    device-seconds scale by the mesh size (one dispatch occupies
    every device). ``stats()["mesh"]`` reports topology plus per-pool
    logical/physical/per-device bytes; ``bigdl_serving_mesh_*``
    gauges carry the same figures.

    LANE STATE: a model some of whose layers keep a fixed recurrent
    state a sequence (``model.has_lane_state``, ``models/hybrid.py``)
    is served with TWO kinds of state in the one cache manager. Its
    pool is ``{"pages": ..., "lanes": ...}``: pages as above for the
    layers that hold K and V, and one state a serving lane (slot
    ``i`` is lane ``i``, plus a scratch lane that idle prefill rows
    write) for the rest. The prefill dispatch is told which lane each
    row fills (a row at position 0 starts from a zero state), the
    fused decode step which lanes are decoding (the others keep their
    state bit for bit). A cached prefix is then its pages AND the state
    at some position: while a prompt prefills, the lane's state is
    copied into a fixed device store (``paging.SnapshotStore``,
    ``SNAPSHOTS_PER_LANE`` x ``max_slots`` of them) whenever its
    position reaches a multiple of ``S_snap`` — the lane state's bytes
    over one token's KV bytes, rounded up to whole prefill chunks, so a
    cached prefix costs at most about twice its pages — and the donated
    ``PrefixEntry`` carries the ones the request kept (the newest, the
    one it resumed from, and the one at the boundary where its prompt
    left the pages it matched). A hit is as long as the
    deepest snapshot at or under the match: admission shares the pages
    up to it, copies it into the lane and prefills from there
    (``serving/state_restore`` / ``serving/state_snapshot`` spans;
    ``stats()["paging"]["state"]``). A preempted request resumes the
    same way, from its deepest snapshot. Not served with lane state
    yet, each refused at construction: a ``draft`` (speculation needs
    the state rolled back on rejection), a host tier for the prefix
    index (demoted pages would come back without their state), a
    ``mesh`` (the lane state has no sharded layout), int8 KV.

    When to prefer this over ``GenerationService``: mixed or long
    decode lengths under concurrent load (no head-of-line blocking on
    batch completion, slots recycle per token), streaming clients
    (tokens surface per iteration, not per finished batch), and
    prefix-heavy traffic (system prompts, few-shot templates,
    multi-turn) — TTFT scales with the NOVEL suffix, not the prompt.

    Every lifecycle transition (submitted → queued → admitted [+
    ``prefix_hit``] → each prefill chunk → first token → per-token
    decode → finished / cancelled / timed-out / stopped / crashed)
    lands in the flight recorder under the handle's ``request_id``;
    ``debug_requests()`` feeds ``GET /debug/requests``, ``healthz()``
    feeds the liveness probe (503 once the loop crashes), and a loop
    crash writes a postmortem JSON (``postmortem_path`` /
    ``$BIGDL_POSTMORTEM_PATH``, default ``bigdl_postmortem.json``)
    before failing the handles.

    RESOURCE OBSERVABILITY: the engine registers its persistent device
    buffers (the page pool's capacity and live bytes, the bytes the
    prefix index retains, params) as named memory pools
    (``observability.memory.register_pool``) so ``/debug/memory``
    attributes HBM by owner; a ``RecompileWatchdog`` samples the
    compile counter every iteration (post-warmup growth — a shape leak
    — raises the recompile-storm alert), and ``slo_objectives`` (a
    list of ``observability.SloObjective`` or kwargs dicts, bound to
    the ``ttft`` / ``inter_token`` / ``queue_wait`` histograms by
    their ``metric`` field) drives an ``SloWatchdog``. Active alerts
    surface in ``stats()["alerts"]`` and flip the ``/healthz`` body to
    ``status: degraded`` while staying HTTP 200.

    USAGE ACCOUNTING: every request is metered by a ``UsageLedger``
    (``observability.accounting``) under the ``tenant=`` it was
    submitted for — queue seconds, prefilled vs prefix-reused prompt
    tokens (and the KV bytes reuse saved), delivered tokens, KV
    byte-seconds held (held page bytes x residency), and
    device-seconds attributed pro-rata from every ragged prefill round
    and fused decode step across the rows each dispatch advanced.
    ``usage_tenants`` caps tenant-label cardinality (overflow folds
    into ``"other"``). Surfaces: ``handle.usage()``,
    ``stats()["usage"]``, ``debug_usage()`` / ``GET /debug/usage``,
    ``request/usage_final`` recorder events, and the
    ``bigdl_serving_tenant_*`` counters. Pure host bookkeeping — the
    jit-compile gauge stays flat with accounting on.
    """

    def __init__(self, model, max_slots: int = 4,
                 max_len: Optional[int] = None, prefill_chunk: int = 16,
                 prefill_budget_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 top_k=None, top_p=None, queue_capacity: int = 64,
                 seed: int = 0, registry=None,
                 service_name: str = "engine", recorder=None,
                 postmortem_path: Optional[str] = None,
                 prefill_rows: int = 1,
                 prefix_cache_bytes: Optional[int] = None,
                 prefix_cache_rows: Optional[int] = None,
                 prefix_host_bytes: Optional[int] = None,
                 prefix_host_rows: Optional[int] = None,
                 prefix_min_tokens: Optional[int] = None,
                 admission_window: int = 4,
                 slo_objectives=None,
                 usage_tenants: int = 32,
                 draft=None,
                 spec_gamma: int = 4,
                 mesh=None,
                 tp_rules=None,
                 model_axis: str = "model",
                 timeseries_interval_s: float = 1.0,
                 timeseries_capacity: int = 600,
                 kv_dtype: Optional[str] = None,
                 weights_dtype: Optional[str] = None,
                 preempt_slack_s: Optional[float] = 0.25,
                 shed_classes=("low",),
                 tenant_rate_limits=None,
                 chaos=None,
                 page_size: Optional[int] = None,
                 max_pages: Optional[int] = None,
                 incident_dir: Optional[str] = None,
                 anomaly_detectors=None,
                 donate_at_prefill_end: Optional[bool] = None):
        from bigdl_tpu.models.transformer import _validate_sampling
        from bigdl_tpu.observability import serving_engine_instruments
        from bigdl_tpu.observability import memory as obs_memory
        from bigdl_tpu.observability.accounting import UsageLedger
        from bigdl_tpu.observability.anomaly import (
            DetectorBank, default_detector_bank,
        )
        from bigdl_tpu.observability.events import default_recorder
        from bigdl_tpu.observability.incidents import IncidentManager
        from bigdl_tpu.observability.instruments import (
            incident_instruments, qos_instruments,
        )
        from bigdl_tpu.observability.slo_budget import SloBudgetTracker
        from bigdl_tpu.observability.watchdog import (
            RecompileWatchdog, SloObjective, SloWatchdog,
        )

        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if admission_window < 1:
            raise ValueError(
                f"admission_window must be >= 1, got {admission_window}")
        _validate_sampling(temperature > 0.0, top_k, top_p)
        for name, val in (("kv_dtype", kv_dtype),
                          ("weights_dtype", weights_dtype)):
            if val is not None and str(val) != "int8":
                raise ValueError(
                    f"{name} must be None (full precision) or 'int8', "
                    f"got {val!r}")
        self.kv_dtype = "int8" if kv_dtype is not None else None
        self.weights_dtype = "int8" if weights_dtype is not None else None
        #: a request offers its prompt's pages to the prefix index when
        #: its PREFILL ends (a request for the same prefix that arrives
        #: while this one decodes is then a hit), not only when it ends.
        #: None: what the model's builder said of its deployment, off
        #: where it said nothing (docs/programming-guide/serving.md has
        #: why off is still the default)
        self._donate_at_prefill_end = bool(
            getattr(model, "donate_at_prefill_end", False)
            if donate_at_prefill_end is None else donate_at_prefill_end)
        if self.weights_dtype == "int8":
            # serve through the int8 clone (nn/quantized Quantizer):
            # Linear weights become int8 codes + per-channel scales in
            # BUFFERS, so the memory-bound decode matmuls stream half
            # the bytes. The clone shares the float source's param
            # paths; under a mesh its int8 buffers replicate (same
            # argument as the int8 draft — correct either way).
            from bigdl_tpu.nn.quantized import Quantizer

            model = Quantizer.quantize(model)
        model.evaluate()
        self.model = model
        #: two kinds of state: some layers keep one recurrent state a
        #: lane beside the pages of the others
        self._lane_state = bool(getattr(model, "has_lane_state", False))
        #: layers that select what they read: the model's host arithmetic
        #: for the decode span's counts (None: every layer reads it all)
        self._read_counts = getattr(model, "decode_read_counts", None)
        if self._read_counts is not None \
                and self._read_counts([0], 1) is None:
            self._read_counts = None
        #: the model's host arithmetic for what a prefill dispatch's
        #: full-attention layers gather of their rows' tables (None: it
        #: has no such layer, or does not say)
        self._chunk_counts = getattr(model, "prefill_read_counts", None)
        if self._chunk_counts is not None \
                and self._chunk_counts([0], 1, 1, 1) is None:
            self._chunk_counts = None
        self._prefill_kv_read = self._prefill_kv_table = 0
        #: the same for a decode dispatch: what a full-attention layer's
        #: step reads of its rows' tables, by the form the step takes
        self._step_counts = getattr(model, "step_read_counts", None)
        if self._step_counts is not None \
                and self._step_counts([0], 1, 1) is None:
            self._step_counts = None
        self._decode_kv_read = self._decode_kv_table = 0
        #: layers that route their tokens over experts: the decode step
        #: hands their routing counts out behind the tokens (0: the
        #: programs of a model without them are what they were)
        self._routed = int(getattr(model, "routed_layers", 0))
        if self._lane_state:
            refused = {
                "draft": (draft is not None, "speculation needs the "
                          "recurrent state rolled back when a proposal "
                          "is rejected"),
                "prefix_host_rows/prefix_host_bytes": (
                    bool(prefix_host_rows or prefix_host_bytes),
                    "a demoted entry's pages would come back without "
                    "the state snapshots that belong to them"),
                "mesh": (mesh is not None, "the lane state has no "
                         "sharded layout"),
                "kv_dtype": (kv_dtype is not None, "its pages are "
                             "served in the weights' dtype"),
            }
            for name, (given, why) in refused.items():
                if given:
                    raise ValueError(
                        f"{type(model).__name__} keeps a recurrent state "
                        f"a lane; {name} is not served with lane state "
                        f"yet: {why}")
        self.max_slots = max_slots
        self.eos_id = eos_id
        self.temperature = temperature
        self.top_k, self.top_p = top_k, top_p
        self.draft = draft
        self._spec = None
        if draft is not None:
            if draft.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft vocab ({draft.vocab_size}) must match the "
                    f"target's ({model.vocab_size}) — acceptance "
                    "compares distributions token-for-token")
            if temperature > 0.0 and (top_k is not None
                                      or top_p is not None):
                raise ValueError(
                    "speculative sampling accepts with min(1, p/q) "
                    "over the UNFILTERED tempered distributions; "
                    "top_k/top_p would break the acceptance identity "
                    "— drop them or drop the draft")
            draft.evaluate()
            self._spec = SpeculationPolicy(spec_gamma)
        self.service_name = service_name
        self.admission_window = admission_window
        #: flight recorder fed by every lifecycle transition (captured
        #: at construction, like the instruments — swap the default
        #: BEFORE building the engine, or pass one explicitly)
        self._rec = recorder if recorder is not None \
            else default_recorder()
        self._registry = registry
        #: crash black-box destination; resolved at crash time
        #: ($BIGDL_POSTMORTEM_PATH, else ./bigdl_postmortem.json)
        self.postmortem_path = postmortem_path
        #: bounded ring of finished-request timeline summaries — the
        #: source for stats() percentiles and /debug/requests "recent".
        #: The lock covers append vs. snapshot: iterating a deque that
        #: another thread appends to raises RuntimeError in CPython,
        #: and /debug readers run on HTTP threads while the loop writes
        self._timelines: collections.deque = collections.deque(
            maxlen=RECENT_TIMELINES)
        self._timelines_lock = threading.Lock()
        self._policy = PrefillPolicy(prefill_chunk, prefill_budget_tokens,
                                     prefill_rows)
        c = self._policy.chunk
        # the cache length rounds the serving window UP to a chunk
        # multiple (the last prefill chunk is padded, and forward_chunk's
        # caller contract is pos0 + chunk <= cache length); if that
        # overflows the model's own context, the window rounds DOWN
        # instead — admission then caps t0 + n at the reduced window.
        cap = min(max_len or model.max_len, model.max_len)
        cache_len = -(-cap // c) * c
        if cache_len > model.max_len:
            cache_len = (model.max_len // c) * c
            cap = cache_len
        if cache_len < c:
            raise ValueError(
                f"prefill_chunk {c} exceeds the usable context {cap}")
        self.max_len = cap
        self._cache_len = cache_len
        # speculation pads every KV row by gamma scratch positions: a
        # verify round launched at the window's last decodable
        # position still writes gamma (possibly rejected) proposal
        # positions past it — headroom instead of a silently-clamping
        # (= prefix-corrupting) dynamic_update_slice. Scratch beyond a
        # row's live prefix is position-masked until overwritten,
        # exactly the slot-reuse argument.
        phys_len = cache_len + (self._spec.kv_headroom
                                if self._spec is not None else 0)
        self._phys_len = phys_len
        if draft is not None and draft.max_len < cap:
            raise ValueError(
                f"draft context ({draft.max_len}) is shorter than the "
                f"engine's serving window ({cap}); shrink max_len or "
                "bring a longer-context draft")

        # ---- the page pool's geometry ----------------------------------
        # EVERY KV surface (slot rows, in-flight prefills, retained
        # prefixes, host tier, draft mirrors) is ONE refcounted block
        # pool per model (serving.paging): requests hold fixed
        # page_size-token pages through BlockTables, prefix hits SHARE
        # the aligned pages instead of copying rows, and eviction /
        # host-tier demotion / preemption-donation are refcount moves.
        # Compiled shapes depend only on (max_pages, page_size).
        if page_size is None:
            # derived, not a knob: 16 is what both chip programs pass,
            # and the gcd keeps prefill_chunk % page_size == 0
            page_size = math.gcd(c, 16)
        page_size = int(page_size)
        if page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {page_size}")
        if c % page_size != 0:
            raise ValueError(
                f"prefill_chunk ({c}) must be a multiple of "
                f"page_size ({page_size}): the chunk-aligned reuse "
                "boundary must land on a page boundary, or a hit's "
                "shared pages would be written under a live share "
                "(the copy-on-write invariant paging.py documents)")
        self.page_size = page_size
        #: fixed device block-table width: every request's table is
        #: padded to the worst-case page count, so compiled shapes
        #: never depend on any one request's length
        self._table_len = -(-phys_len // page_size)
        if max_pages is None:
            # room for every slot at full length plus an equal
            # retained-prefix share
            max_pages = 1 + 2 * max_slots * self._table_len
        max_pages = int(max_pages)
        if max_pages < 1 + self._table_len:
            raise ValueError(
                f"max_pages ({max_pages}) cannot hold one "
                f"full-length request ({self._table_len} pages) "
                "plus the reserved scratch page")

        # ---- tensor-parallel mesh (SPMD serving) -----------------------
        # With a mesh, EVERY compiled program below runs as one SPMD
        # dispatch: params are Megatron-sharded (transformer_tp_rules /
        # shard_params), the page pools (target and draft) shard their
        # KV-HEADS dim along the model axis (the layout the
        # column-parallel QKV writes with no collective), host inputs
        # enter replicated, and jit/GSPMD places the row-parallel
        # all-reduces. Host-side control flow (scheduler, streams,
        # ledger, recorder) stays mesh-oblivious.
        self.mesh = mesh
        self.model_axis = model_axis
        self._kv_shard = self._d_kv_shard = self._repl = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from bigdl_tpu.parallel.tp import transformer_tp_rules

            self._kv_shard = model.kv_page_pool_sharding(
                mesh, model_axis=model_axis)
            if draft is not None:
                try:
                    self._d_kv_shard = draft.kv_page_pool_sharding(
                        mesh, model_axis=model_axis)
                except ValueError as e:
                    raise ValueError(
                        f"draft model cannot shard over this mesh: "
                        f"{e}") from None
            self._repl = NamedSharding(mesh, PartitionSpec())
            if tp_rules is None:
                tp_rules = transformer_tp_rules(model_axis)
        self._tp_rules = tp_rules

        self._params = jax.tree.map(jnp.asarray, model.params_dict())
        self._buffers = jax.tree.map(jnp.asarray, model.buffers_dict())
        if mesh is not None:
            from bigdl_tpu.parallel.tp import replicate, shard_params

            self._params = shard_params(self._params, mesh, tp_rules)
            self._buffers = replicate(self._buffers, mesh)
        dtype = model.tok_embed.dtype
        # THE page pool: one persistent (max_pages, page_size,
        # heads * head_dim) buffer set per layer, donated through
        # every dispatch; page and offset lead, so the KV write is
        # an in-place scatter of whole rows (nn/attention.py
        # _write_kv_paged).
        # There is no separate staging cache — admissions prefill
        # straight through their reserved tables — and no separate
        # prefix pool: retained prefixes are refcounted shares of
        # these same pages.
        if self._lane_state:
            # lane i is slot i; the last lane is scratch: idle prefill
            # rows write it (the lanes' page 0)
            self._kv_pool = model.init_page_pool(
                max_pages, page_size, dtype=dtype,
                lanes=max_slots + 1)
        else:
            self._kv_pool = model.init_page_pool(
                max_pages, page_size, dtype=dtype,
                sharding=self._kv_shard, kv_dtype=self.kv_dtype)
        self._pages = PagePool(self._kv_pool, page_size)
        self._tables: List[Optional[BlockTable]] = [None] * max_slots
        self._d_kv_pool = self._d_pages = self._d_tables = None
        if draft is not None:
            self._d_params = jax.tree.map(jnp.asarray,
                                          draft.params_dict())
            self._d_bufs = jax.tree.map(jnp.asarray,
                                        draft.buffers_dict())
            if mesh is not None:
                from bigdl_tpu.parallel.tp import (
                    replicate, shard_params,
                )

                # same rule set: an int8 clone shares the float
                # source's param paths; unmatched leaves (quantizer
                # scales, layernorms) replicate — correct either way
                self._d_params = shard_params(self._d_params, mesh,
                                              tp_rules)
                self._d_bufs = replicate(self._d_bufs, mesh)
            d_dtype = draft.tok_embed.dtype
            # the draft's own page pool, tables of the same width (so
            # lifecycle stays lockstep though head counts/dims may
            # differ): it never shares pages (the prefix index retains
            # target KV only), so at most max_slots concurrent tables —
            # sized to always satisfy a reservation the target pool
            # accepted
            self._d_kv_pool = draft.init_page_pool(
                1 + max_slots * self._table_len, page_size,
                dtype=d_dtype, sharding=self._d_kv_shard,
                kv_dtype=self.kv_dtype)
            self._d_pages = PagePool(self._d_kv_pool, page_size)
            self._d_tables = [None] * max_slots
        # the prefix index's budgets keep their row currency: one "row"
        # is what a full-length request holds (table_len pages). Summed
        # over the LIVE pool leaves, so under kv_dtype="int8" this is
        # the true quantized physical cost — int8 code buffers PLUS the
        # f32 scale sidecars — and everything derived from it
        # (token_bytes, entry/host budgets, the ledger's bytes_saved
        # credits) stays honest without a special case
        row_bytes = self._table_len * self._pages.page_bytes
        self._row_bytes = row_bytes
        #: device KV bytes one cached token position costs — the
        #: exchange rate prefix-reuse savings are credited at
        self._token_bytes = row_bytes / phys_len
        if prefix_cache_rows is not None:
            max_entries = max(0, int(prefix_cache_rows))
        elif prefix_cache_bytes is None:
            max_entries = 2 * max_slots
        else:
            max_entries = max(0, int(prefix_cache_bytes) // row_bytes)
        # host tier behind the device pool: reclaimed entries spill to
        # pinned host buffers instead of dropping (0 = tier off)
        if prefix_host_rows is not None:
            host_rows = max(0, int(prefix_host_rows))
        elif prefix_host_bytes is None:
            host_rows = 0
        else:
            host_rows = max(0, int(prefix_host_bytes) // row_bytes)
        # ---- lane state: the snapshot store and its stride ------------
        self._snaps: Optional[SnapshotStore] = None
        self._snap_store = None
        self._snap_stride = 0
        if self._lane_state:
            lanes = lane_leaves(self._kv_pool)
            lane_bytes = sum(int(l.nbytes) for l in jax.tree.leaves(lanes)
                             ) // (max_slots + 1)
            #: tokens between two snapshots of a prefilling lane: what a
            #: snapshot costs in tokens of KV, in whole chunks
            self._snap_stride = c * max(1, math.ceil(
                lane_bytes / self._token_bytes / c))
            n_snaps = SNAPSHOTS_PER_LANE * max_slots
            # a snapshot is a lane's leaves FLAT: the store pays a
            # state's logical bytes, whatever tiles the lanes' own
            # layout pads to (the copies re-lay one lane's worth)
            self._snap_store = jax.tree.map(
                lambda l: jnp.zeros((n_snaps, math.prod(l.shape[1:])),
                                    l.dtype), lanes)
            self._snaps = SnapshotStore(n_snaps, lane_bytes)
        self._prefix: Optional[PagedPrefixIndex] = None
        if max_entries > 0:
            # max_entries bounds ENTRY count (cardinality), the shared
            # page pool bounds bytes; the host budget converts to pages
            self._prefix = PagedPrefixIndex(
                self._pages, max_entries=max_entries,
                min_tokens=(prefix_min_tokens
                            if prefix_min_tokens is not None else c),
                token_bytes=self._token_bytes,
                # pages shard over the MODEL axis only: each device's
                # share is logical / model_shards (a 2-D mesh's data
                # axis replicates them, so mesh.size would undercount)
                devices=(int(mesh.shape[model_axis])
                         if mesh is not None else 1),
                host_pages=host_rows * self._table_len,
                snapshots=self._snaps)
        self._prefix_evictions_seen = 0
        self._prefix_demotions_seen = 0
        self._prefix_host_evictions_seen = 0
        #: host-side prompt-token tally actually prefilled by THIS
        #: engine (the reused-fraction denominator — per-instance
        #: exact, unlike the shared-label registry counter)
        self._prefilled_tokens = 0
        #: per-instance speculative tallies (the stats() numerator/
        #: denominator — the registry counters are shared per label)
        self._spec_proposed = 0
        self._spec_accepted = 0
        #: programs that have run at least once (a first dispatch's
        #: wall is mostly compile time and is charged as cold)
        self._warm = set()
        #: page bookkeeping: last KV byte-second accrual stamp, the
        #: page-flow counter baselines behind the delta-published
        #: bigdl_serving_page_* instruments, and the blocked-admission
        #: latch (set when the pool cannot satisfy the queue head's
        #: reservation this iteration — re-probed next iteration
        #: instead of thrashing pop/requeue within one)
        self._last_kv_accrue: Optional[float] = None
        self._page_seen = {"allocated": 0, "shared": 0,
                           "cow_forks": 0, "freed": 0}
        self._adm_blocked = False
        self._build_fns()

        self._ins = serving_engine_instruments(service_name, registry)
        #: per-request / per-tenant usage meter: queue wait, prefill
        #: vs prefix-reused tokens, delivered tokens, KV byte-seconds
        #: held, and device-seconds attributed pro-rata per dispatch.
        #: Pure host bookkeeping — zero device programs, so the
        #: jit-compile gauge stays flat with accounting on.
        self._usage = UsageLedger(
            service=service_name, registry=registry, recorder=self._rec,
            instruments=self._ins, max_tenants=usage_tenants,
            recent=USAGE_RECENT,
            token_bytes=self._token_bytes,
            devices=(int(mesh.size) if mesh is not None else 1))
        self._queue = AdmissionQueue(
            queue_capacity, recorder=self._rec,
            wait_histogram=self._ins.queue_wait_seconds)
        self._slots: List[Optional[_SlotState]] = [None] * max_slots
        self._adms: List[_Admission] = []
        self._key = jax.random.PRNGKey(seed)
        self._zero_key = self._h2d(jax.random.PRNGKey(0))
        #: the compiled programs' temperature operand, committed once
        #: (it is fixed per engine) — rebuilding a replicated scalar
        #: per decode iteration would put a host->mesh transfer in the
        #: hot loop for a constant
        self._temp_const = self._h2d(jnp.float32(
            self.temperature if self.temperature > 0.0 else 1.0))

        self._ins.slots.set(max_slots, force=True)
        # numerics telemetry: which dtypes the hot path runs, plus the
        # honest per-row physical bytes (scale sidecars included) next
        # to the full-precision row the same geometry would cost — the
        # before/after pair behind the quantized-capacity claim
        self._fp_row_bytes = int(
            model.kv_token_elems() * phys_len * jnp.dtype(dtype).itemsize)
        self._ins.quantized_kv.set(
            1 if self.kv_dtype else 0, force=True)
        self._ins.quantized_weights.set(
            1 if self.weights_dtype else 0, force=True)
        self._ins.kv_row_bytes.set(row_bytes, force=True)

        # ---- resource observability -----------------------------------
        # per-pool HBM attribution: every persistent device buffer set
        # this engine owns, registered under weakrefs (the monitor must
        # never keep a dead engine's KV pools alive). Names are keyed
        # by service_name; a same-named successor engine takes them over.
        # attribution is PHYSICAL: tree_device_bytes sums every leaf's
        # per-device shards, so a mesh engine's sharded KV pools report
        # their true global footprint while replicated leaves (most of
        # params) count once per device — identical to tree_bytes for
        # an unsharded engine, honest for an SPMD one. Figures are
        # SNAPSHOTTED here, the one moment the donated trees cannot be
        # mid-dispatch (shapes/shardings never change afterwards):
        # walking a live donated tree's shards from a monitor/HTTP
        # thread could observe an already-deleted buffer and raise.
        self._pool_bytes = self._snapshot_pool_bytes()

        def pool_reader(key):
            return lambda e: e._pool_bytes[key]["physical_bytes"]

        pools = {f"serving/{service_name}/{key}": pool_reader(key)
                 for key in self._pool_bytes}
        # the page pool's LIVE footprint next to its capacity: bytes
        # of pages something still references (slot tables, in-flight
        # admissions, prefix entries) — /debug/memory then answers
        # "how full is the pool" not just "how big"
        pools[f"serving/{service_name}/kv_pages_in_use"] = (
            lambda e: e._pages.bytes_in_use)
        if self.draft is not None:
            pools[f"serving/{service_name}/draft_pages_in_use"] = (
                lambda e: e._d_pages.bytes_in_use)
        if self._prefix is not None:
            # "prefix KV actually retained" (pro-rata over shares) and,
            # with a host tier, "who owns the spill" in the same table
            pools[f"serving/{service_name}/prefix_kv_in_use"] = (
                lambda e: e._prefix.bytes_in_use)
            if self._prefix.host_pages > 0:
                pools[f"serving/{service_name}/prefix_host_kv"] = (
                    lambda e: e._prefix.host_bytes_in_use)
        self._memory_pools = obs_memory.register_owned_pools(self, pools)

        # mesh topology gauges + per-pool per-device footprint
        n_dev = int(mesh.size) if mesh is not None else 1
        shards = (int(mesh.shape[model_axis])
                  if mesh is not None else 1)
        self._ins.mesh_devices.set(n_dev, force=True)
        self._ins.mesh_model_shards.set(shards, force=True)
        for pool_name, summary in self._pool_bytes.items():
            self._ins.mesh_pool_bytes_per_device.labels(
                service_name, pool_name).set(
                    summary["bytes_per_device"], force=True)

        # ---- dispatch cost model / loop-phase attribution --------------
        # static per-kind FLOPs/bytes extracted ONCE here via
        # jitted.lower(...).cost_analysis(): lowering only traces — no
        # compile, no execution, donated buffers stay live — so the
        # extraction adds zero device programs and the jit-compile
        # gauge stays flat. When XLA reports nothing the analytic
        # transformer formulas take over (flops_source: "analytic").
        self._cost = DispatchCostModel(
            device_peaks(self._cost_device()), devices=n_dev)
        self._loop_obs = LoopPhaseAccumulator()
        self._extract_program_costs()
        #: counter children + flushed totals for the per-phase series
        self._loop_phase_counters = {
            p: self._ins.loop_phase_seconds.labels(service_name, p)
            for p in LoopPhaseAccumulator.PHASES}
        self._loop_flushed = {p: 0.0
                              for p in LoopPhaseAccumulator.PHASES}
        # background gauge/rate sampler behind /debug/timeseries and
        # /debug/dashboard; started with the loop thread, joined in
        # stop() — bounded rings, no-op when the registry is disabled
        self._ts = TimeSeriesSampler(
            interval_s=timeseries_interval_s,
            capacity=timeseries_capacity, registry=registry)
        self._ts.add_source("mfu", lambda: self._cost.rates("decode")[0])
        self._ts.add_source(
            "mfu_prefill", lambda: self._cost.rates("prefill")[0])
        self._ts.add_source("tokens_per_sec",
                            self._ins.decode_tokens_total.get, rate=True)
        self._ts.add_source(
            "slot_occupancy",
            lambda: (sum(s is not None for s in self._slots)
                     / max(1, self.max_slots)))
        self._ts.add_source("queue_depth", lambda: len(self._queue))
        if self._spec is not None:
            self._ts.add_source(
                "acceptance_rate",
                lambda: (self._spec_accepted / self._spec_proposed
                         if self._spec_proposed else None))
        # the pool gauges, charted: occupancy (live references over
        # usable pages) and reservation fragmentation
        self._ts.add_source(
            "page_pool_occupancy",
            lambda: (self._pages.pages_in_use
                     / max(1, self._pages.max_pages - 1)))
        self._ts.add_source("page_fragmentation", self._fragmentation)
        self._ts.add_source("alerts", lambda: float(len(self.alerts())))

        # ---- anomaly detection + incident capture ----------------------
        # detectors see every appended sampler point (observer runs on
        # the sampler thread and only RECORDS triggers — the engine
        # loop drains them once per iteration and does the capture
        # work there); watchdog alerts and chaos drills converge on
        # the same trigger stream in _process_triggers. Host-side
        # Python only — the jit gauge stays flat with capture on.
        if anomaly_detectors is None:
            self._bank = default_detector_bank()
        elif isinstance(anomaly_detectors, DetectorBank):
            self._bank = anomaly_detectors
        else:
            self._bank = DetectorBank(anomaly_detectors)
        self._ts.set_observer(self._bank.observe)
        self._incidents = IncidentManager(
            service_name, recorder=self._rec, registry=registry,
            dirpath=incident_dir, cooldown_s=INCIDENT_COOLDOWN_S,
            config={"service_name": service_name,
                    "max_slots": max_slots, "max_len": self.max_len,
                    "prefill_chunk": self._policy.chunk,
                    "admission_window": admission_window,
                    "kv_dtype": self.kv_dtype,
                    "weights_dtype": self.weights_dtype,
                    "shed_classes": list(shed_classes or ()),
                    "preempt_slack_s": preempt_slack_s})
        self._inc_ins = incident_instruments(registry)
        self._det_gauges: Dict[str, object] = {}
        self._trig_counters: Dict[str, object] = {}

        # watchdogs, sampled once per loop iteration: compiles that keep
        # growing after warmup break the engine's shape-stability
        # contract (storm alert); SLO objectives burn against the TTFT /
        # inter-token / queue-wait histograms. Alerts surface through
        # stats()["alerts"] and a degraded (but 200) /healthz body.
        self._recompile_wd = RecompileWatchdog(
            self._compile_total, service=service_name,
            registry=registry, recorder=self._rec)
        self._slo_wd = SloWatchdog(service=service_name,
                                   registry=registry, recorder=self._rec)
        slo_children = {"ttft": self._ins.ttft_seconds,
                        "inter_token": self._ins.inter_token_seconds,
                        "queue_wait": self._ins.queue_wait_seconds}
        # the error-budget ledger reads the SAME histogram children as
        # the watchdog: the watchdog answers "burning now?", the
        # tracker answers "how much budget is left / when does it run
        # out" — and chaos burn drills spend it synthetically so the
        # exhaustion path is exercisable
        self._slo_budget = SloBudgetTracker(
            service=service_name, registry=registry, recorder=self._rec)
        for obj in (slo_objectives or ()):
            if isinstance(obj, dict):
                obj = SloObjective(**obj)
            if obj.metric not in slo_children:
                raise ValueError(
                    f"SloObjective {obj.name!r} names unknown engine "
                    f"metric {obj.metric!r}; expected one of "
                    f"{sorted(slo_children)}")
            self._slo_wd.watch(obj, slo_children[obj.metric])
            self._slo_budget.watch(obj, slo_children[obj.metric])
        # stats() reports the DELTA since construction (the same
        # registry-façade convention as OccupancyStats): two engines
        # sharing a service_name share the series, so each instance
        # snapshots its own baseline
        self._stats_base = {k: self._counter(k).get()
                            for k in ("admitted", "finished", "evicted",
                                      "timed_out", "cancelled")}

        # ---- QoS: preemption, burn-rate shedding, token buckets --------
        # preemption: a HIGH-class request queued past this slack with
        # no free slot evicts the lowest-class longest-remaining slot,
        # donating its KV to the prefix index so the automatic resume
        # re-prefills only the uncached tail (None disables)
        if preempt_slack_s is not None and preempt_slack_s < 0:
            raise ValueError(f"preempt_slack_s must be >= 0 or None, "
                             f"got {preempt_slack_s}")
        self.preempt_slack_s = preempt_slack_s
        # shed set under an active TTFT burn: "low" sheds the moment
        # the alert raises; "normal" (opt-in) sheds only once the burn
        # passes TWICE its alert threshold (severe). "high" is never
        # sheddable — that is what the class buys.
        self.shed_classes = tuple(shed_classes or ())
        for p in self.shed_classes:
            if p not in PRIORITY_RANK or p == "high":
                raise ValueError(
                    f"shed_classes may contain 'low'/'normal', "
                    f"got {p!r}")
        # per-tenant device-second token buckets (post-paid): keys are
        # resolved tenant names, "*" sets the default for every tenant
        # without an explicit entry; values are (rate_per_s, burst)
        # tuples or {"rate": ..., "burst": ...} dicts. None = unlimited.
        self._rate_limits = dict(tenant_rate_limits or {})
        self._buckets: Dict[str, TokenBucket] = {}
        self._buckets_lock = threading.Lock()
        for tenant in self._rate_limits:
            if tenant != "*":
                self._tenant_bucket(self._usage.resolve_tenant(tenant))
        #: scripted fault injector (serving.chaos.ChaosInjector): the
        #: shed decision honors its synthetic burn, the loop honors
        #: its dispatch faults and slot freezes. None = no injection.
        self._chaos = chaos
        self._qos_ins = qos_instruments(registry)
        # host-side QoS tallies (per-instance exact — the registry
        # counters are shared per label and carry dynamic class/tenant
        # labels, so stats() keeps its own figures)
        self._qos_counts = {"preempted": 0, "shed": 0,
                            "rate_limited": 0}

        self._wake = threading.Condition()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lifecycle = threading.Lock()
        self._crashed: Optional[BaseException] = None
        self._draining = False

    # ------------------------------------------------- compiled programs
    def _decode_form(self, pool) -> str:
        """How one decode token meets the pages of ``pool`` (the
        engine's, or its draft's), from what the engine can see. On a
        mesh heads stay a batch dimension of both products
        (``"heads"``: the rows form would sum over the sharded heads
        dimension, two collectives a layer). On one TPU chip a kernel
        reads the pages where they lie (``"kernel"``,
        ops/paged_attention.py), where EVERY layer that takes the word
        can be read so: a full-attention layer's pool (a tuple a layer)
        the float pair of whole tiles, a latent layer's (one bare leaf)
        whole tiles of floats; the int8 4-tuple and a toy model's
        narrow pages are not, and one such layer keeps the whole model
        off the kernel. A selecting layer's pool is a dict and has its
        own step. Everywhere else every table is gathered: K and V
        stay rows of H_kv * D under a block-diagonal q, a latent
        layer's rows meet its whole-row q (``"rows"``: nn/attention.py
        _attend_pages_rows, nn/latent_attention.py)."""
        if self.mesh is not None:
            return "heads"
        if jax.default_backend() != "tpu":
            return "rows"
        # (importing Pallas takes a second: only where the kernel can run)
        from bigdl_tpu.ops.paged_attention import supported

        def readable(entry):
            if isinstance(entry, tuple):
                return len(entry) == 2 and supported(entry[0])
            return supported(entry)

        told = [g for g in page_leaves(pool) if not isinstance(g, dict)]
        kept = [(i, g) for i, g in enumerate(told) if not readable(g)]
        if told and not kept:
            return "kernel"
        if kept:
            # said out loud, once a pool: the gathered form is the slow
            # one here, and only stats()["paging"] would show it
            at, entry = kept[0]
            logging.getLogger(__name__).warning(
                "engine %s: decode attention keeps the gathered \"rows\" "
                "form for the whole model: the paged-attention kernel "
                "cannot read pool entry %d of %d as it lies (%s)",
                self.service_name, at, len(told), jax.tree.map(
                    lambda a: f"{a.dtype}{list(a.shape)}", entry))
        return "rows"

    def _build_fns(self):
        """The compiled programs: every KV surface is the page pool,
        gathered/scattered through per-request block tables INSIDE the
        dispatch. Compiled shapes depend only on
        ``(max_pages, page_size)`` and the fixed dispatch widths
        (max_slots / prefill_rows / table_len / gamma) — none on load —
        so the jit gauge stays flat while alloc/share/COW-fork/evict/
        demote/preempt move nothing but host-side refcounts."""
        from bigdl_tpu.models.transformer import (
            _filter_logits, _spec_accept,
        )
        from bigdl_tpu.nn.module import bind, scoped

        model = self.model
        sampled = self.temperature > 0.0
        top_k, top_p = self.top_k, self.top_p
        # how one decode token meets its pages, decided here once for
        # every program below that runs decode_step_paged
        attend = self._decode_attention = self._decode_form(self._kv_pool)

        lane_state = self._lane_state
        # rows that are no request move no lane's state and take no
        # expert's slot: such models are told which rows are live
        routed = bool(self._routed)
        masked = lane_state or routed

        @scoped("sample")
        def sample0(logits, rng, temperature):
            if sampled:
                return jax.random.categorical(
                    rng, _filter_logits(logits, temperature, top_k,
                                        top_p),
                    axis=-1).astype(jnp.int32)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def step(p, bufs, tok, pos, pool, tables, rng, temperature,
                 *active):
            # one fused decode over ALL slots; idle lanes carry the
            # all-scratch table (SCRATCH_PAGE padding) so their junk
            # write lands on page 0, never on a live page. A lane's
            # recurrent state has no scratch to park on: ``active``
            # (lane-state and routed models) masks it inside the program
            kw = {"active": active[0]} if masked else {}
            if routed:
                kw["routing"] = True
            with bind(model, p, bufs, False, None):
                logits, pool, *counts = model.decode_step_paged(
                    tok, pos, pool, tables, decode_attention=attend,
                    **kw)
            nxt = sample0(logits, rng, temperature)
            # a routed model's counts ride behind the tokens: one vector,
            # one transfer
            return (jnp.concatenate([nxt, *counts]) if counts else nxt), pool

        def chunk(p, bufs, ids, pool, tables, pos0, last_idx, *lanes):
            # the ragged admission prefill, writing through each row's
            # reserved table: a prefix hit's row starts at pos0 = base
            # (page-aligned — see the ctor's chunk/page check), so its
            # writes land only in its FRESH pages while the shared head
            # is read via the gather — zero row copies on the hit leg.
            # ``lanes`` (lane-state models only): the lane each row's
            # recurrent state lives in, the scratch lane for idle rows
            kw = {"lanes": lanes[0]} if lane_state else {}
            with bind(model, p, bufs, False, None):
                return model.prefill_chunk_at_paged(ids, pool, tables,
                                                    pos0, last_idx, **kw)

        def one_row(a, row):
            return jax.lax.dynamic_slice(
                a, (row,) + (0,) * (a.ndim - 1), (1,) + a.shape[1:])

        def row_copy(d, s, dst_row, src_row):
            return jax.lax.dynamic_update_slice(
                d, one_row(s, src_row).astype(d.dtype),
                (dst_row,) + (jnp.int32(0),) * (d.ndim - 1))

        def copy_page(pool, dst, src):
            # single-page pool-internal copy — the COW privatization
            # primitive (BlockTable.ensure_writable's copy_page
            # callback) — one compiled signature, load-independent.
            # Pages only: a pool's lane state is not rows of pages
            return with_pages(pool, jax.tree.map(
                lambda b: row_copy(b, b, dst, src), page_leaves(pool)))

        def copy_row(dst, src, dst_row, src_row):
            # generic tree row copy, kept for the promote landing:
            # (1, ...) host-transferred page tree -> pool page dst_row
            return with_pages(dst, jax.tree.map(
                lambda d, s: row_copy(d, s, dst_row, src_row),
                page_leaves(dst), src))

        def restore_state(pool, store, lane, sid):
            # admission on a hit: snapshot sid (flat) becomes lane's state
            return with_lanes(pool, jax.tree.map(
                lambda l, s: row_copy(
                    l, one_row(s, sid).reshape((1,) + l.shape[1:]), lane, 0),
                lane_leaves(pool), store))

        def snapshot_state(store, pool, sid, lane):
            # a prefilling lane's state kept at a stride boundary
            return jax.tree.map(
                lambda s, l: row_copy(
                    s, one_row(l, lane).reshape(1, -1), sid, 0),
                store, lane_leaves(pool))

        # On a mesh, output shardings are PINNED: every program's pool
        # leaves with the same NamedSharding it entered with (and
        # scalars/logits leave replicated), so the donated buffers
        # cycle through the loop in ONE stable layout. Left to GSPMD's
        # own choice, an output can drift (e.g. to replicated), and the
        # next dispatch's changed input sharding compiles a fresh
        # signature — a gauge-visible leak. Target and draft pools
        # share the spec (heads along the model axis), so one prefix
        # broadcast covers every pool tree.
        kv, repl = self._kv_shard, self._repl

        def _jit(fn, donate, out=None):
            if self.mesh is None:
                # graftlint: ok[jit-hazard] — meshless (single-device) branch has no shardings to pin
                return jax.jit(fn, donate_argnums=donate)
            return jax.jit(fn, donate_argnums=donate, out_shardings=out)

        self._step_jit = _jit(step, (4,), (repl, kv))
        self._chunk_jit = _jit(chunk, (3,), (repl, kv))
        self._copy_page_jit = _jit(copy_page, (0,), kv)
        self._copy_row_jit = _jit(copy_row, (0,), kv)
        self._sample0_jit = _jit(sample0, (), repl)
        self._restore_jit = self._snapshot_jit = None
        if lane_state:
            self._restore_jit = _jit(restore_state, (0,))
            self._snapshot_jit = _jit(snapshot_state, (0,))

        self._take_row_jit = None
        if self._prefix is not None and self._prefix.host_pages > 0:
            def take_row(src, row):
                # demotion source: one jitted slice lifting page `row`
                # out as a (1, ...) tree the spill bulk-copies to host
                return jax.tree.map(
                    lambda s: jax.lax.dynamic_slice(
                        s, (row,) + (0,) * (s.ndim - 1),
                        (1,) + s.shape[1:]), src)

            self._take_row_jit = _jit(take_row, (), kv)

        # ---- speculative-decoding programs --------------------------
        self._propose_jit = self._spec_verify_jit = None
        self._d_chunk_jit = self._d_sync_jit = None
        if self.draft is not None:
            draft = self.draft
            g = self._spec.gamma
            d_attend = self._decode_form(self._d_kv_pool)

            # the draft proposer IS the standalone speculative path's
            # cached lax.scan: (max_slots,) tokens at (max_slots,)
            # per-row positions, gamma draft steps, ONE dispatch, draft
            # KV written as it goes
            self._propose_jit = draft._propose_fn_paged(
                self.max_slots, g, self._table_len, sampled=sampled,
                cache_sharding=self._d_kv_shard,
                repl_sharding=self._repl, decode_attention=d_attend)

            def d_chunk(p, bufs, ids, pool, tables, pos0, last_idx):
                # the draft's mirror of the ragged admission prefill:
                # same chunk geometry, its own pool; the gathered
                # logits are discarded (the first token always samples
                # from the TARGET's prefill logits)
                with bind(draft, p, bufs, False, None):
                    return draft.prefill_chunk_at_paged(
                        ids, pool, tables, pos0, last_idx)

            def d_sync(p, bufs, tok, pos, pool, tables):
                # one ragged draft step re-writing each row's LAST
                # accepted token's KV at its own position: for rows
                # that accepted everything this fills the one position
                # the propose scan never wrote (the gamma-th proposal's
                # KV); for every other row it rewrites identical values
                # in place (same token, same position -> same KV), so
                # one fixed-shape dispatch serves all rows
                with bind(draft, p, bufs, False, None):
                    _, pool = draft.decode_step_paged(
                        tok, pos, pool, tables, decode_attention=d_attend)
                return pool

            def spec_verify(p, bufs, tok, props, qlogits, pos, pool,
                            tables, rng, temperature):
                # ONE ragged target forward scores every row's
                # proposals (the verify_chunk path): chunk column 0 is
                # the row's pending token (its KV is written first),
                # columns 1..g its proposals; logits column j predicts
                # the token at position pos+j+1. Acceptance is decided
                # per ROW in-graph so the host transfer is just the
                # (S, g+1) emit matrix + (S,) accepted counts.
                chunk_ids = jnp.concatenate(
                    [tok[:, None], jnp.swapaxes(props, 0, 1)], axis=1)
                with bind(model, p, bufs, False, None):
                    logits, pool = model.verify_chunk_paged(
                        chunk_ids, pool, tables, pos)
                with jax.named_scope("sample"):
                    if sampled:
                        accept, resid, bonus = _spec_accept(
                            logits, jnp.swapaxes(qlogits, 0, 1),
                            chunk_ids[:, 1:], temperature, rng)
                        n_acc = jnp.sum(jnp.cumprod(
                            accept.astype(jnp.int32), axis=1), axis=1)
                        # emit column j: the proposal while accepted; at
                        # the first rejection the residual draw, on full
                        # acceptance the bonus draw (columns past n_acc
                        # are never read by the host)
                        fix = jnp.take_along_axis(
                            jnp.concatenate([resid, bonus[:, None]],
                                            axis=1),
                            n_acc[:, None], axis=1)
                        cols = jnp.arange(g + 1)[None, :]
                        padded = jnp.concatenate(
                            [chunk_ids[:, 1:],
                             jnp.zeros_like(tok)[:, None]], axis=1)
                        emit = jnp.where(cols < n_acc[:, None], padded, fix)
                    else:
                        v_tok = jnp.argmax(logits, axis=-1).astype(
                            jnp.int32)
                        match = (chunk_ids[:, 1:] == v_tok[:, :g]).astype(
                            jnp.int32)
                        n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                        # matched proposals ARE the target argmax, so the
                        # emitted burst is v_tok[:, :n_acc+1] verbatim —
                        # exactly the tokens the non-speculative engine
                        # would have argmaxed one step at a time
                        emit = v_tok
                return emit, n_acc, pool

            self._d_chunk_jit = _jit(d_chunk, (3,), (repl, kv))
            self._d_sync_jit = _jit(d_sync, (4,), kv)
            self._spec_verify_jit = _jit(spec_verify, (6,),
                                         (repl, repl, kv))

        # warm every copy/transfer signature NOW (page 0 onto page 0 —
        # the scratch page, harmless): COW copies, demote slices, and
        # promote scatters first fire deep into steady state, and a
        # compile there would read as a post-warmup jit_compiles bump —
        # the flatness contract the gauge polices
        z = jnp.int32(0)
        self._kv_pool = self._copy_page_jit(self._kv_pool, z, z)
        self._warm.add("copy:page")
        if lane_state:
            # the scratch lane into free snapshot 0 and back: both
            # copies first fire on traffic (a stride boundary, a hit)
            scratch = jnp.int32(self.max_slots)
            self._snap_store = self._snapshot_jit(
                self._snap_store, self._kv_pool, z, scratch)
            self._kv_pool = self._restore_jit(
                self._kv_pool, self._snap_store, scratch, z)
            self._warm.update(("state:snapshot", "state:restore"))
        if self._take_row_jit is not None:
            from bigdl_tpu.parallel.tp import put_from_host

            _ = self._take_row_jit(self._kv_pool, z)
            host_proto = jax.tree.map(
                lambda s: np.zeros((1,) + s.shape[1:], s.dtype),
                self._kv_pool)
            one_page = put_from_host(host_proto, self._kv_shard)
            self._kv_pool = self._copy_row_jit(self._kv_pool, one_page,
                                               z, z)
            self._warm.update(("copy:demote", "copy:promote"))
        if self.draft is not None:
            # warm the whole speculative round (all-scratch tables:
            # every junk write lands on page 0): the sync dispatch is
            # CONDITIONAL at runtime (it only fires when some row
            # fully accepts), so left cold it could first compile many
            # iterations after warmup and read as a recompile storm.
            # Warmed inputs take the SAME layout runtime inputs will
            # (replicated-committed on a mesh, via _h2d): a layout
            # mismatch would make the first real dispatch a second
            # compile — exactly the flatness the gauge polices
            zt = self._h2d(jnp.zeros((self.max_slots,), jnp.int32))
            zT = self._h2d(jnp.zeros(
                (self.max_slots, self._table_len), jnp.int32))
            zk = self._h2d(jax.random.PRNGKey(0))
            t1 = self._h2d(jnp.float32(1.0))
            props, qlogits, self._d_kv_pool = self._propose_jit(
                self._d_params, self._d_bufs, zt, zt,
                self._d_kv_pool, zT, zk, t1)
            _, _, self._kv_pool = self._spec_verify_jit(
                self._params, self._buffers, zt, props, qlogits, zt,
                self._kv_pool, zT, zk, t1)
            self._d_kv_pool = self._d_sync_jit(
                self._d_params, self._d_bufs, zt, zt,
                self._d_kv_pool, zT)
            self._warm.update(("spec:propose", "spec:verify",
                               "spec:sync"))

    def _h2d(self, x):
        """Host value → device array; on a mesh, committed REPLICATED.
        Every per-iteration host input (token/position vectors, chunk
        ids, RNG keys, the temperature scalar) funnels through here so
        compiled signatures see ONE stable input layout — GSPMD never
        has to guess a fresh sharding per call, and the jit gauge
        stays flat."""
        x = jnp.asarray(x)
        if self._repl is not None:
            x = jax.device_put(x, self._repl)
        return x

    def _pool_trees(self) -> dict:
        """Short name → live buffer tree for every persistent device
        pool this engine owns (the mesh-summary / per-device gauge
        enumeration; keys match the ``serving/<name>/<pool>`` registry
        suffixes)."""
        out = {"kv_page_pool": page_leaves(self._kv_pool),
               "params": self._params}
        if self._lane_state:
            out["lane_state"] = lane_leaves(self._kv_pool)
            out["state_snapshots"] = self._snap_store
        if self.draft is not None:
            out["draft_page_pool"] = self._d_kv_pool
            out["draft_params"] = self._d_params
        return out

    def _mesh_summary(self) -> dict:
        """The ``stats()["mesh"]`` block: topology (axis names/sizes,
        device count, which axis shards the model) and per-pool byte
        attribution — logical bytes (the array's global shape),
        physical bytes (shards summed across devices; replicated
        leaves count once per device), and the per-device share one
        chip's HBM actually pays. Pool shapes and shardings are
        load-independent, so the figures are computed ONCE at
        construction (``_snapshot_pool_bytes``) — also why this is
        safe from HTTP/debug threads: reading a live donated tree's
        shards mid-dispatch could observe a deleted buffer."""
        n = int(self.mesh.size) if self.mesh is not None else 1
        out = {"enabled": self.mesh is not None, "devices": n,
               "pools": dict(self._pool_bytes)}
        if self.mesh is not None:
            out["axes"] = {str(a): int(s)
                           for a, s in self.mesh.shape.items()}
            out["model_axis"] = self.model_axis
            out["model_shards"] = int(self.mesh.shape[self.model_axis])
        return out

    def _snapshot_pool_bytes(self) -> dict:
        """Per-pool byte attribution, computed at construction while
        no loop thread can be mid-donation (every later reader serves
        this snapshot — the buffers' shapes and shardings never change
        for the engine's life)."""
        from bigdl_tpu.observability import memory as obs_memory

        n = int(self.mesh.size) if self.mesh is not None else 1
        pools = {}
        for name, tree in self._pool_trees().items():
            logical = obs_memory.tree_bytes(tree)
            physical = obs_memory.tree_device_bytes(tree)
            pools[name] = {
                "logical_bytes": logical,
                "physical_bytes": physical,
                "bytes_per_device": physical // n,
                "sharded": bool(n > 1 and physical < logical * n),
            }
        return pools

    def _compile_total(self) -> int:
        fns = [self._step_jit, self._chunk_jit, self._copy_row_jit,
               self._sample0_jit, self._copy_page_jit]
        if self._take_row_jit is not None:
            fns.append(self._take_row_jit)
        if self._lane_state:
            fns += [self._restore_jit, self._snapshot_jit]
        if self.draft is not None:
            fns += [self._propose_jit, self._spec_verify_jit,
                    self._d_chunk_jit, self._d_sync_jit]
        return sum(int(f._cache_size()) for f in fns)

    # --------------------------------------------------- dispatch costs
    def _cost_device(self):
        """The device whose peak table entry prices this engine's
        dispatches: mesh device 0 when sharded, local device 0
        otherwise."""
        if self.mesh is not None:
            return self.mesh.devices.flat[0]
        return jax.local_devices()[0]

    def _extract_program_costs(self) -> None:
        """Price every dispatch kind ONCE: sum XLA ``cost_analysis``
        over the kind's programs (prefill = target chunk [+ draft
        chunk]; decode = fused step, or propose + verify under
        speculation), lowered against the live buffers — tracing only,
        zero compiles, zero executions.  Any program the lowering does
        not price (every program, when the target is a TPU) drops the
        whole kind to the analytic transformer formulas at a
        representative context of half the cache, with a warning."""
        S, rows = self.max_slots, self._policy.prefill_rows
        c = self._policy.chunk
        zt = self._h2d(jnp.zeros((S,), jnp.int32))
        zk = self._h2d(jax.random.PRNGKey(0))
        t1 = self._temp_const
        ids = self._h2d(jnp.zeros((rows, c), jnp.int32))
        rpos = self._h2d(jnp.zeros((rows,), jnp.int32))
        zT = self._h2d(jnp.zeros((S, self._table_len), jnp.int32))
        zTr = self._h2d(jnp.zeros((rows, self._table_len),
                                  jnp.int32))
        lanes_arg, active_arg = (), ()
        if self._lane_state:
            lanes_arg = (self._h2d(jnp.zeros((rows,), jnp.int32)),)
        if self._lane_state or self._routed:
            active_arg = (self._h2d(jnp.zeros((S,), bool)),)
        progs = {"prefill": [(self._chunk_jit,
                              (self._params, self._buffers, ids,
                               self._kv_pool, zTr, rpos, rpos)
                              + lanes_arg)]}
        if self.draft is None:
            progs["decode"] = [(self._step_jit,
                                (self._params, self._buffers, zt,
                                 zt, self._kv_pool, zT, zk, t1)
                                + active_arg)]
        else:
            progs["prefill"].append(
                (self._d_chunk_jit,
                 (self._d_params, self._d_bufs, ids,
                  self._d_kv_pool, zTr, rpos, rpos)))
            try:
                props_sd, qlog_sd, _ = jax.eval_shape(
                    self._propose_jit, self._d_params,
                    self._d_bufs, zt, zt, self._d_kv_pool, zT,
                    zk, t1)
            except Exception:
                props_sd = qlog_sd = None
            progs["decode"] = [
                (self._propose_jit,
                 (self._d_params, self._d_bufs, zt, zt,
                  self._d_kv_pool, zT, zk, t1))]
            if props_sd is not None:
                progs["decode"].append(
                    (self._spec_verify_jit,
                     (self._params, self._buffers, zt, props_sd,
                      qlog_sd, zt, self._kv_pool, zT, zk, t1)))
        ctx = self._phys_len // 2
        g = self._spec.gamma if self._spec is not None else 0
        analytic = {
            "prefill": (rows * c, ctx),
            "decode": (S * (g + 1) if g else S, ctx),
        }
        cache_itemsize = int(jax.tree.leaves(page_leaves(self._kv_pool))[0]
                             .dtype.itemsize)
        for kind, entries in progs.items():
            costs = [program_cost(fn, *args) for fn, args in entries]
            if all(cst is not None for cst in costs):
                self._cost.set_program_cost(
                    kind, sum(cst["flops"] for cst in costs),
                    sum(cst["bytes"] for cst in costs), "xla")
                continue
            # said out loud, once per engine: a TPU-targeted lowering
            # prices nothing (only the executable does), so on the chip
            # this is the source — never a silent change from "xla"
            logging.getLogger(__name__).warning(
                "engine %s: XLA priced no FLOPs for the lowered %s "
                "program(s) on %s; flops_source is \"analytic\" "
                "(TransformerLM.analytic_flops / analytic_bytes)",
                self.service_name, kind,
                self._cost.peaks["device_kind"])
            tokens, c_ctx = analytic[kind]
            flops = self.model.analytic_flops(tokens, c_ctx)
            byts = self.model.analytic_bytes(tokens, c_ctx,
                                             cache_itemsize)
            if self.draft is not None:
                # the draft's share of the kind: its own chunk during
                # prefill, gamma propose steps during decode
                d_tok = rows * c if kind == "prefill" else S * g
                flops += self.draft.analytic_flops(d_tok, c_ctx)
                byts += self.draft.analytic_bytes(d_tok, c_ctx,
                                                  cache_itemsize)
            self._cost.set_program_cost(kind, flops, byts, "analytic")

    # ------------------------------------------------------- lifecycle
    def start(self) -> "ContinuousBatchingEngine":
        """Start the loop thread (idempotent; ``submit`` auto-starts)."""
        with self._lifecycle:
            if self._crashed is not None:
                raise EngineStopped(
                    "engine loop crashed; construct a new engine"
                ) from self._crashed
            if self._thread is None or not self._thread.is_alive():
                self._stop_evt.clear()
                self._thread = threading.Thread(
                    target=self._loop, name="serving-engine", daemon=True)
                self._thread.start()
            self._ts.start()
        return self

    def stop(self, drain: bool = True,
             timeout: Optional[float] = 30.0) -> None:
        """Stop the loop thread. ``drain=True`` first waits (up to
        ``timeout``) for queued + running requests to finish; any
        request still unfinished when the loop halts fails with
        ``EngineStopped``."""
        if drain and self._thread is not None and self._thread.is_alive():
            deadline = (time.monotonic() + timeout
                        if timeout is not None else None)
            while self._has_work():
                if self._crashed is not None or (
                        deadline is not None
                        and time.monotonic() > deadline):
                    break
                time.sleep(0.002)
        self._stop_evt.set()
        self._ts.stop()
        with self._wake:
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                # the loop is wedged inside a device dispatch: leave
                # its slot/admission state alone (mutating it under a
                # live loop would crash the loop on resume) — it will
                # observe _stop_evt and exit when the dispatch returns;
                # call stop() again then to fail the leftovers
                return
        err = EngineStopped("engine stopped before the request finished")
        for h in self._queue.drain():
            self._finish_handle(h, err, "stopped")
        for a in self._adms:
            self._free_admission_tables(a)
            self._finish_handle(a.handle, err, "stopped")
        self._adms = []
        for sid, st in enumerate(self._slots):
            if st is not None:
                self._release_snaps(st.snaps)
                self._finish_handle(st.handle, err, "stopped")
                self._slots[sid] = None
            self._free_slot_table(sid)
        # leak invariant: after the tables above and the index's
        # retained entries release their references, every page is
        # back on the free list (pages_in_use == 0 — tested)
        if self._prefix is not None:
            self._prefix.drop_all()
        self._sync_page_gauges()

    def drain(self) -> None:
        """Stop admitting NEW requests while everything already
        submitted (queued, prefilling, decoding) runs to completion —
        the loop keeps iterating, the slots empty out on their own.
        Further ``submit`` calls raise ``EngineDraining`` until
        ``resume()``; a fleet supervisor uses this pair to take a
        degraded replica out of rotation without dropping a single
        in-flight request. Idempotent; observable as
        ``healthz()["draining"]``."""
        if self._draining:
            return
        self._draining = True
        self._rec.record("engine/drain", self.service_name,
                         service=self.service_name,
                         in_flight=len(self._queue) + len(self._adms)
                         + sum(s is not None for s in self._slots))

    def resume(self) -> None:
        """Lift a ``drain()``: the engine admits new requests again
        (the rejoin half of the fleet drain lifecycle). Idempotent."""
        if not self._draining:
            return
        self._draining = False
        self._rec.record("engine/resume", self.service_name,
                         service=self.service_name)

    @property
    def draining(self) -> bool:
        return self._draining

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc, tb):
        self.stop(drain=exc_type is None)

    def _has_work(self) -> bool:
        return (len(self._queue) > 0 or len(self._adms) > 0
                or any(s is not None for s in self._slots))

    # ---------------------------------------------------------- client
    def submit(self, prompt_ids, max_new_tokens: int,
               timeout_s: Optional[float] = None, block: bool = True,
               queue_timeout_s: Optional[float] = None,
               tenant: Optional[str] = None,
               priority: str = "normal",
               trace_id: Optional[str] = None) -> RequestHandle:
        """Queue one request (1-D prompt). Returns its handle
        immediately; stream with ``handle.tokens()`` or block on
        ``handle.result()``. ``timeout_s`` is a wall deadline covering
        queue + prefill + decode (expiry raises ``RequestTimedOut`` from
        the handle — including while blocked on a full queue); a full
        admission queue blocks (``block=True``, up to
        ``queue_timeout_s``) or raises ``QueueFull``.

        ``tenant`` names the workload the request's usage is billed to
        (the usage ledger's attribution key and the
        ``bigdl_serving_tenant_*`` label; ``None`` bills to
        ``"default"``). The first ``usage_tenants`` distinct names get
        their own series; later new names fold into ``"other"`` — the
        cardinality cap that keeps the label space bounded no matter
        what clients send. ``handle.usage()`` returns the request's
        metered consumption.

        ``priority`` (``"high"``/``"normal"``/``"low"``) is the QoS
        class: admission orders by (class, deadline slack, prefix
        score) with per-class starvation bounds; a waiting high-class
        request may PREEMPT a lower-class slot (the victim resumes
        token-identical); under an active TTFT burn the shed set
        (``shed_classes``) is refused with ``RequestShed``, and a
        tenant past its token bucket with ``RequestRateLimited`` —
        both carry ``retry_after_s``.

        ``trace_id`` is the distributed-trace correlation id (the
        fleet front door mints one per request, honoring an inbound
        ``traceparent``): the handle and the usage record carry it,
        and the recorder binds it so EVERY flight-recorder event of
        this request — queue, prefill, per-token decode, terminal —
        is joinable across processes in the merged fleet trace."""
        if self._crashed is not None:
            raise EngineStopped("engine loop crashed") from self._crashed
        if self._draining:
            raise EngineDraining(
                "engine is draining: in-flight requests are finishing "
                "but new submissions are refused (resume() to rejoin)")
        prompt = np.asarray(prompt_ids, np.int32)
        if prompt.ndim != 1:
            raise ValueError("submit takes ONE request (1-D prompt), "
                             f"got shape {prompt.shape}")
        t0, n = prompt.shape[0], int(max_new_tokens)
        if n < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if t0 < 1 or t0 + n > self.max_len:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({n}) exceeds the "
                f"engine's serving window {self.max_len}")
        # the request's FULL page reservation (admission reserves
        # the whole span eagerly — the no-mid-flight-OOM contract)
        # must fit the pool even with every other page free
        g = self._spec.gamma if self._spec is not None else 0
        need = pages_needed(min(t0 + n + g, self._phys_len),
                            self.page_size)
        usable = self._pages.max_pages - 1  # page 0 is scratch
        if need > usable:
            raise ValueError(
                f"request needs {need} KV pages but the pool only "
                f"has {usable} allocatable (max_pages="
                f"{self._pages.max_pages} minus the scratch page) "
                f"— raise max_pages or shorten the request")
        self.start()
        h = RequestHandle(prompt, n, timeout_s, priority=priority)
        if trace_id is not None:
            h.trace_id = trace_id
            # one binding covers the request's whole recorded arc —
            # every layer that records with this request_id (queue,
            # loop, usage ledger) inherits the trace attr for free
            self._rec.bind_request(h.request_id, trace=trace_id)
        h._usage = self._usage.begin(h.request_id, tenant, t0, n,
                                     submitted_at=h.submitted_at)
        h._usage.trace_id = trace_id
        h.tenant = h._usage.tenant
        self._rec.record("request/submitted", h.request_id,
                         service=self.service_name, prompt_tokens=t0,
                         max_new_tokens=n, tenant=h.tenant,
                         priority=priority)
        # ---- QoS gates, cheapest-first: burn-rate shedding, then the
        # tenant's token bucket. Both are structured rejections (the
        # handle finishes through the _finish_handle funnel with its
        # outcome, the ledger bills the queue-side life, the front
        # door maps them to 429 + Retry-After) — never silent drops.
        shed = self._shed_state()
        if shed["active"] and priority in shed["classes"]:
            retry = self._shed_retry_after_s(shed)
            err = RequestShed(
                f"shed at admission: TTFT SLO burning at "
                f"{shed['burn_rate']:.1f}x budget "
                f"({shed['source']}), class {priority!r} is in the "
                f"shed set — retry in {retry:.2f}s",
                retry_after_s=retry)
            self._reject_qos(h, err, "shed")
            raise err
        bucket = self._tenant_bucket(h.tenant)
        if bucket is not None and not bucket.try_admit():
            retry = bucket.retry_after()
            err = RequestRateLimited(
                f"tenant {h.tenant!r} exhausted its device-second "
                f"budget (bucket {bucket.level():.3f}s, refill "
                f"{bucket.rate:.3f}/s) — retry in {retry:.2f}s",
                retry_after_s=retry)
            self._reject_qos(h, err, "rate_limited")
            raise err
        try:
            self._queue.put(h, block=block, timeout=queue_timeout_s)
        except Exception as e:
            # close the ledger, then the timeline — a backpressure
            # rejection must not read as a request that vanished
            # mid-flight, and the outcome event stays the LAST event
            # of the request's recorded arc (same order as
            # _finish_handle)
            self._usage.finalize(h._usage, "rejected",
                                 time.monotonic())
            self._rec.record("request/rejected", h.request_id,
                             service=self.service_name,
                             error=type(e).__name__)
            if isinstance(e, RequestTimedOut):
                self._ins.timed_out_total.inc()
            raise
        with self._wake:
            self._wake.notify_all()
        # submit can race stop() or a loop crash: if the loop died
        # between our start() and the put (both paths drain the queue
        # from the dying side, so a put landing after that drain would
        # otherwise strand the handle forever), drain-and-fail now
        # rather than hand back a handle nobody will ever finish
        if self._crashed is not None or (
                self._stop_evt.is_set()
                and (self._thread is None
                     or not self._thread.is_alive())):
            err = EngineStopped("engine stopped while the request was "
                                "being submitted")
            if self._crashed is not None:
                err.__cause__ = self._crashed
            for dropped in self._queue.drain():
                self._finish_handle(dropped, err, "stopped")
            self._finish_handle(h, err, "stopped")
            raise err
        return h

    # ------------------------------------------------------ QoS plumbing
    def _shed_state(self) -> dict:
        """The load-shedding decision input: is the TTFT SLO burning
        (really — an active SloWatchdog alert on a ``metric="ttft"``
        objective — or synthetically via the chaos injector), how
        hard, and which priority classes shed as a result. ``low``
        sheds on any active burn; ``normal`` (when opted into
        ``shed_classes``) only once the burn is SEVERE (>= 2x its
        alert threshold); ``high`` never sheds."""
        active = severe = False
        burn = 0.0
        source = None
        if self._chaos is not None and self._chaos.burn_active():
            active = True
            severe = self._chaos.burn_severe()
            burn = 4.0 if severe else 2.0
            source = "chaos"
        else:
            for row in self._slo_wd.state():
                if row["metric"] != "ttft" or not row["active"]:
                    continue
                active = True
                burn = max(burn, row["burn_rate"])
                severe = severe or row["severe"]
                source = "slo:" + row["objective"]
        classes = ()
        if active:
            classes = (self.shed_classes if severe else
                       tuple(p for p in self.shed_classes
                             if p == "low"))
        return {"active": active and bool(classes), "severe": severe,
                "burn_rate": burn, "source": source,
                "classes": classes}

    def _shed_retry_after_s(self, shed: dict) -> float:
        """Back-off hint for a shed rejection: long enough for the
        trailing burn window to move, doubled under a severe burn."""
        return 2.0 if shed["severe"] else 1.0

    def _tenant_bucket(self, tenant: str):
        """The tenant's device-second token bucket, created lazily
        from ``tenant_rate_limits`` (exact name first, then the
        ``"*"`` default); None = unlimited."""
        if not self._rate_limits:
            return None
        with self._buckets_lock:
            b = self._buckets.get(tenant)
            if b is not None:
                return b
            cfg = self._rate_limits.get(tenant,
                                        self._rate_limits.get("*"))
            if cfg is None:
                return None
            if isinstance(cfg, dict):
                b = TokenBucket(cfg["rate"], cfg["burst"])
            else:
                rate, burst = cfg
                b = TokenBucket(rate, burst)
            self._buckets[tenant] = b
            return b

    def _reject_qos(self, h: RequestHandle, err: Exception,
                    outcome: str) -> None:
        """Terminal bookkeeping for a structured QoS rejection
        (shed / rate_limited): through the ``_finish_handle`` funnel —
        the ledger bills the queue-side life under the real outcome,
        the ``request/shed`` / ``request/rate_limited`` event stays
        the last of the request's recorded arc, and the
        ``(class, tenant)``-labelled QoS counter increments. The
        caller raises ``err`` to the submitter."""
        self._qos_counts[outcome] += 1
        getattr(self._qos_ins, outcome + "_total").labels(
            self.service_name, h.priority, h.tenant).inc()
        self._finish_handle(h, err, outcome)

    def _finish_handle(self, h: RequestHandle,
                       err: Optional[BaseException],
                       outcome: str) -> None:
        """Terminal bookkeeping for ONE request — recorder event,
        stream sentinel, finished-timeline ring entry. Every lifecycle
        exit (finished / cancelled / timed_out / stopped / crashed)
        funnels through here so the flight recorder and the stats()
        percentiles can never disagree with the handles. ``_finish``
        arbitrates racing finishers (a stopping submitter vs. the
        crashing loop) — only the winner records."""
        if not h._finish(err):
            return
        rec = getattr(h, "_usage", None)
        if rec is not None:
            # the usage ledger's terminal funnel shares _finish's
            # arbitration: exactly one finalizer closes residencies,
            # bills the tenant, and records request/usage_final —
            # BEFORE the outcome event, which stays the last event of
            # every request's recorded timeline (tested contract)
            self._usage.finalize(rec, outcome, h.finished_at)
            # post-paid rate limiting: the bucket consumes the
            # request's MEASURED device-seconds at the same terminal
            # point the ledger bills them
            bucket = self._tenant_bucket(rec.tenant)
            if bucket is not None and rec.device_s > 0:
                bucket.debit(rec.device_s)
        # a preemption pin that never reached re-admission (the victim
        # finished/cancelled/timed out while requeued) must not leak a
        # pinned prefix entry
        pin = h.__dict__.pop("_preempt_pin", None)
        if pin is not None and self._prefix is not None:
            self._prefix.release(pin)
        self._rec.record("request/" + outcome, h.request_id,
                         service=self.service_name,
                         tokens=len(h._tokens),
                         tenant=getattr(h, "tenant", None))
        tl = h.timeline()
        tl["request_id"] = h.request_id
        tl["outcome"] = outcome
        tl["tenant"] = getattr(h, "tenant", None)
        tl["trace_id"] = getattr(h, "trace_id", None)
        tl["page_waited"] = bool(getattr(h, "_page_waited", False))
        with self._timelines_lock:
            self._timelines.append(tl)

    def _counter(self, key: str):
        return getattr(self._ins, key + "_total")

    def stats(self) -> dict:
        """Operational façade over the registry series (same pattern —
        and same shared-``service_name`` caveat — as the batch
        services' ``stats()``): flow counters are the delta since THIS
        engine was constructed. ``latency`` adds per-phase percentile
        summaries (queue wait / prefill / TTFT / decode / total,
        each ``{count, mean, p50, p90, p99}``) computed from the
        engine's recent finished-request timelines; ``prefix_cache``
        adds the cache's hit rate, reused-token fraction, and current
        byte occupancy (per-instance exact — the cache object belongs
        to this engine); ``usage`` adds the ledger's per-tenant
        attribution table and the engine goodput block (device
        seconds by kind, occupancy-weighted utilization, padding
        waste, tokens per device-second); ``cost`` adds the dispatch
        cost model's per-kind FLOPs/bytes, achieved FLOP/s and
        bytes/s, MFU/bandwidth-utilization fractions, and the
        compute-vs-memory-bound roofline class; ``loop`` adds the
        loop-phase breakdown attributing the device-idle fraction to
        named host-side bubbles."""
        out = {k: int(self._counter(k).get() - base)
               for k, base in self._stats_base.items()}
        out["active_slots"] = sum(s is not None for s in self._slots)
        out["queue_depth"] = len(self._queue)
        out["jit_compiles"] = self._compile_total()
        out["latency"] = self._latency_summary()
        out["prefix_cache"] = self._prefix_summary()
        out["speculation"] = self._spec_summary()
        out["quantization"] = self._quant_summary()
        out["mesh"] = self._mesh_summary()
        out["usage"] = self._usage.summary()
        out["cost"] = self._cost.summary()
        out["loop"] = self._loop_obs.summary()
        out["slo_budget"] = self._slo_budget.state()
        out["capacity"] = self._capacity_summary(
            loop=out["loop"], cost=out["cost"], usage=out["usage"])
        out["qos"] = self._qos_summary()
        out["paging"] = self._paging_summary()
        out["alerts"] = self.alerts()
        out["incidents"] = {"count": self._incidents.total,
                            "by_kind": self._incidents.counts_by_kind()}
        return out

    def _qos_summary(self) -> dict:
        """The ``stats()["qos"]`` block: shedding state (is the TTFT
        SLO burning, which classes shed), the preempted / shed /
        rate-limited tallies, queue composition by class, and each
        provisioned tenant bucket's balance."""
        shed = self._shed_state()
        with self._buckets_lock:
            buckets = {t: b.snapshot()
                       for t, b in sorted(self._buckets.items())}
        out = {
            "shedding": {"active": shed["active"],
                         "severe": shed["severe"],
                         "burn_rate": round(shed["burn_rate"], 3),
                         "source": shed["source"],
                         "classes": list(shed["classes"])},
            "shed_classes_configured": list(self.shed_classes),
            "preempt_slack_s": self.preempt_slack_s,
            "queue_by_class": self._queue.depth_by_class(),
            "rate_limits": buckets,
            **self._qos_counts,
        }
        if self._chaos is not None:
            out["chaos"] = self._chaos.snapshot()
        return out

    def alerts(self) -> List[dict]:
        """The active watchdog alerts (recompile storm, SLO burns) as
        plain dicts — empty while the engine is healthy. The same list
        rides in ``stats()["alerts"]`` and the ``/healthz`` body."""
        out = []
        storm = self._recompile_wd.alert()
        if storm is not None:
            out.append(storm)
        out.extend(self._slo_wd.alerts())
        return out

    def _prefix_summary(self) -> dict:
        if self._prefix is None:
            return {"enabled": False}
        ps = self._prefix.stats()
        prefilled = self._prefilled_tokens
        denom = ps["reused_tokens"] + prefilled
        return {
            "enabled": True,
            **ps,
            "prefilled_tokens": prefilled,
            "reused_fraction": (round(ps["reused_tokens"] / denom, 4)
                                if denom else 0.0),
        }

    def _quant_summary(self) -> dict:
        """The ``stats()["quantization"]`` block: which numerics the
        hot path runs and what one full-length request's pages
        physically cost — ``kv_row_bytes`` (scale sidecars included)
        next to ``fp_row_bytes`` (the same geometry at full precision),
        whose ratio is the capacity multiplier quantization bought
        (pages per HBM byte scale by its inverse)."""
        return {
            "kv_dtype": self.kv_dtype or "fp",
            "weights_dtype": self.weights_dtype or "fp",
            "kv_row_bytes": int(self._row_bytes),
            "fp_row_bytes": int(self._fp_row_bytes),
            "row_bytes_ratio": (round(self._row_bytes
                                      / self._fp_row_bytes, 4)
                                if self._fp_row_bytes else 1.0),
        }

    def _spec_summary(self) -> dict:
        """The ``stats()["speculation"]`` block: per-instance proposed
        vs accepted draft-token tallies and the acceptance rate (the
        gamma-tuning signal — a rate near 1 says raise gamma, a rate
        near 0 says the draft disagrees with the target and every
        round degenerates to one corrected token)."""
        if self._spec is None:
            return {"enabled": False}
        prop = self._spec_proposed
        return {
            "enabled": True,
            "gamma": self._spec.gamma,
            "proposed_tokens": prop,
            "accepted_tokens": self._spec_accepted,
            "acceptance_rate": (round(self._spec_accepted / prop, 4)
                                if prop else 0.0),
        }

    def _latency_summary(self) -> dict:
        from bigdl_tpu.observability.events import percentile_summary

        with self._timelines_lock:
            snap = list(self._timelines)
        tls = [t for t in snap if t.get("outcome") == "finished"]
        return {phase: percentile_summary(
                    t[phase + "_s"] for t in tls)
                for phase in ("queue_wait", "prefill", "ttft",
                              "decode", "total")}

    def healthz(self) -> dict:
        """Liveness probe for ``MetricsHTTPServer(healthz=...)``: a
        status dict while the engine is serviceable, raising
        ``EngineStopped`` once the loop thread has crashed — the
        endpoint then flips to 503 instead of reporting a dead decode
        loop as healthy. While a watchdog alert is active the body
        carries ``status: degraded`` plus the alert list — still HTTP
        200 (the engine serves; 503 remains the crashed-loop signal),
        so orchestrators keep routing while operators see the fire."""
        if self._crashed is not None:
            raise EngineStopped(
                f"engine loop crashed: {self._crashed!r}"
            ) from self._crashed
        alerts = self.alerts()
        return {
            # always present: direct callers key on it, not only the
            # HTTP handler (which would merge in an "ok" of its own)
            "status": "degraded" if alerts else "ok",
            "engine": self.service_name,
            "loop_alive": bool(self._thread is not None
                               and self._thread.is_alive()),
            "active_slots": sum(s is not None for s in self._slots),
            "queue_depth": len(self._queue),
            # machine-readable drain state: a fleet supervisor keys on
            # status (degraded -> drain) + draining (rejoin gate) + the
            # in-flight count (drain completion), never on body prose
            "draining": self._draining,
            "in_flight": (len(self._queue) + len(self._adms)
                          + sum(s is not None for s in self._slots)),
            # compact QoS posture: is load shedding live right now,
            # and how much traffic has been preempted/shed/throttled
            # so far — the full picture lives in stats()["qos"]
            "qos": {"shedding": self._shed_state()["active"],
                    **self._qos_counts},
            "alerts": alerts,
        }

    def debug_requests(self) -> dict:
        """The ``/debug/requests`` payload: every in-flight request's
        id, phase, and progress, the recent finished timelines with
        their queue-wait/prefill/TTFT/decode breakdown (now including
        per-request ``prefix_tokens``), the percentile summary over
        them, and the prefix-cache occupancy/hit-rate block. Snapshot
        semantics — safe to call from an HTTP thread while the loop
        runs."""
        now = time.monotonic()
        in_flight = []
        for h in self._queue.snapshot():
            in_flight.append({
                "request_id": h.request_id, "state": "queued",
                "age_s": now - h.submitted_at,
                "prompt_tokens": int(h.prompt.shape[0]),
                "max_new_tokens": h.max_new_tokens,
                "tenant": getattr(h, "tenant", None),
                "priority": h.priority, "preempted": h.preempted,
            })
        for adm in list(self._adms):
            h = adm.handle
            row = {
                "request_id": h.request_id, "state": "prefill",
                "age_s": now - h.submitted_at,
                "prompt_tokens": int(h.prompt.shape[0]),
                "max_new_tokens": h.max_new_tokens,
                "tenant": getattr(h, "tenant", None),
                "priority": h.priority, "preempted": h.preempted,
                "chunks_done": adm.next_chunk,
                "chunks_total": adm.n_chunks,
                "staging_row": adm.row,
                "prefix_tokens": adm.base,
            }
            if self.draft is not None:
                row["draft_chunks_done"] = adm.d_next_chunk
                row["draft_chunks_total"] = adm.d_n_chunks
            in_flight.append(row)
        for sid, st in enumerate(list(self._slots)):
            if st is None:
                continue
            h = st.handle
            in_flight.append({
                "request_id": h.request_id, "state": "decoding",
                "slot": sid, "age_s": now - h.submitted_at,
                "prompt_tokens": int(h.prompt.shape[0]),
                "max_new_tokens": h.max_new_tokens,
                "tenant": getattr(h, "tenant", None),
                "priority": h.priority, "preempted": h.preempted,
                "tokens_delivered": st.delivered,
            })
        with self._timelines_lock:
            recent = list(self._timelines)[-50:]
        return {"service": self.service_name,
                "in_flight": in_flight,
                "recent": recent,
                "latency": self._latency_summary(),
                "prefix_cache": self._prefix_summary(),
                "speculation": self._spec_summary(),
                "mesh": self._mesh_summary(),
                "alerts": self.alerts()}

    def debug_usage(self, top_n: int = 10) -> dict:
        """The ``GET /debug/usage`` payload: the per-tenant usage
        table (tokens, queue seconds, device-seconds, KV
        byte-seconds, prefix savings), engine-wide totals, the
        goodput block, and the top-``top_n`` recently finished
        requests by attributed device-seconds. Snapshot semantics —
        safe from HTTP threads while the loop runs."""
        return {"service": self.service_name,
                **self._usage.summary(top_n=top_n)}

    def debug_timeseries(self, metric: Optional[str] = None,
                         n: Optional[int] = None) -> dict:
        """The ``GET /debug/timeseries?metric=&n=`` payload: the
        background sampler's bounded rings (MFU, tokens/s, slot
        occupancy, queue depth, acceptance rate, alert count) as
        ``{metric: {points: [[monotonic_ts, value], ...], last}}``.
        Snapshot semantics — safe from HTTP threads."""
        return {"service": self.service_name,
                "running": self._ts.running,
                **self._ts.snapshot(metric=metric, n=n)}

    def debug_incidents(self, n: Optional[int] = None) -> dict:
        """The ``GET /debug/incidents[?n=]`` payload: the newest
        ``n`` captured bundles plus the lifetime count and per-kind
        tallies. Snapshot semantics — safe from HTTP threads while
        the loop runs; the same shape ships over the fleet's
        ``incident_export`` RPC."""
        n = 10 if n is None else int(n)
        return {"service": self.service_name,
                "count": self._incidents.total,
                "by_kind": self._incidents.counts_by_kind(),
                "detectors": self._bank.states(),
                "incidents": self._incidents.snapshot(n)}

    def _capacity_summary(self, loop=None, cost=None,
                          usage=None) -> dict:
        """The ``stats()["capacity"]`` block: the what-if model over
        this engine's measured loop / cost / usage summaries."""
        from bigdl_tpu.observability.capacity import estimate_capacity

        return estimate_capacity(
            loop if loop is not None else self._loop_obs.summary(),
            cost if cost is not None else self._cost.summary(),
            usage if usage is not None else self._usage.summary(),
            max_slots=self.max_slots, service=self.service_name)

    def debug_capacity(self) -> dict:
        """The ``GET /debug/capacity`` payload: the capacity/what-if
        estimate plus the error-budget ledger — everything an
        autoscaling policy (or an operator sizing a fleet) reads.
        Snapshot semantics — safe from HTTP threads."""
        return {"service": self.service_name,
                "capacity": self._capacity_summary(),
                "slo_budget": self._slo_budget.state()}

    def dashboard(self) -> str:
        """The ``GET /debug/dashboard`` page: one self-contained HTML
        document (inline CSS + SVG sparklines, zero external assets)
        over the sampler rings, plus the live cost/roofline, loop
        bubble, and alert blocks. Captured incidents and fired
        triggers draw vertical markers on every sparkline; watched
        SLO objectives draw error-budget bars under the grid."""
        markers = [{"ts_s": t.get("ts_s"), "kind": "alert",
                    "label": t.get("detector")}
                   for t in self._incidents.history()]
        markers += [{"ts_s": b.get("ts_s"), "kind": "incident",
                     "label": "%s (%s)" % (b.get("id"),
                                           b.get("kind"))}
                    for b in self._incidents.snapshot()]
        markers.sort(key=lambda m: m.get("ts_s") or 0.0)
        return render_dashboard(
            self._ts.snapshot(), title=self.service_name,
            extra={"alerts": self.alerts() or None,
                   "incidents": (self._incidents.counts_by_kind()
                                 or None),
                   "cost": self._cost.summary(),
                   "loop": self._loop_obs.summary(),
                   "capacity": self._capacity_summary()},
            markers=markers,
            budgets=self._slo_budget.budget_bars() or None)

    # ------------------------------------------------------- loop body
    def _loop(self):
        try:
            while not self._stop_evt.is_set():
                # idle engines BLOCK (submit/stop notify the condition;
                # IDLE_WAIT_S is only a lost-wakeup safety net) instead
                # of spinning no-op iterations that would burn CPU and
                # flood the tracer/iteration metrics. An empty engine
                # has no deadlines to sweep — queued deadlines imply
                # _has_work() and a live loop.
                with self._wake:
                    while (not self._stop_evt.is_set()
                           and not self._has_work()):
                        with trace.span("serving/idle_wait"):
                            self._wake.wait(IDLE_WAIT_S)
                if self._stop_evt.is_set():
                    break
                with trace.span("serving/iteration",
                                histogram=self._ins.iteration_seconds):
                    self._iterate()
                self._ins.iterations_total.inc()
        except BaseException as e:  # donated buffers may be gone: crash
            self._crash(e)

    def _crash(self, e: BaseException) -> None:
        with self._lifecycle:
            self._crashed = e
            # as stop() does: a crashed engine's sampler thread must
            # not outlive it (nobody is obliged to call stop() on a dead
            # engine); under the lock start() takes to start it
            self._ts.stop()
        self._rec.record("engine/crash", service=self.service_name,
                         error=repr(e))
        # capture the in-flight picture BEFORE failing the handles —
        # the postmortem must show what the engine was doing when it
        # died, not the already-cleaned-up aftermath
        try:
            states = self.debug_requests()["in_flight"]
        except Exception:
            states = []
        self._write_postmortem(e, states)
        # the crash is itself an incident: same evidence pipeline as
        # the anomaly/watchdog triggers, kind "crash" — a fleet
        # supervisor aggregating incident_export sees the dead
        # replica's last picture without reading its postmortem file
        self._capture_incident(
            {"detector": "engine", "metric": "loop", "kind": "crash",
             "reason": f"engine loop crashed: {e!r}",
             "ts_s": time.monotonic(), "value": 1.0, "score": 1.0},
            error=e)
        err = EngineStopped(f"engine loop crashed: {e!r}")
        err.__cause__ = e
        for a in self._adms:
            self._free_admission_tables(a)
            self._finish_handle(a.handle, err, "crashed")
        self._adms = []
        for sid, st in enumerate(self._slots):
            if st is not None:
                self._release_snaps(st.snaps)
                self._finish_handle(st.handle, err, "crashed")
                self._slots[sid] = None
            self._free_slot_table(sid)
        if self._prefix is not None:
            self._prefix.drop_all()
        for h in self._queue.drain():
            self._finish_handle(h, err, "crashed")

    def _write_postmortem(self, e: BaseException,
                          states: List[dict]) -> None:
        """Best-effort crash black box — the crash path must never
        raise (donated buffers are already gone; all that is left is
        to preserve the evidence)."""
        import os

        from bigdl_tpu.observability.postmortem import write_postmortem

        path = (self.postmortem_path
                or os.environ.get("BIGDL_POSTMORTEM_PATH")
                or "bigdl_postmortem.json")
        try:
            write_postmortem(
                path, error=e, requests=states, recorder=self._rec,
                registry=self._registry,
                context={"service": self.service_name,
                         "max_slots": self.max_slots,
                         "max_len": self.max_len,
                         "queue_depth": len(self._queue),
                         "stats": {k: int(self._counter(k).get() - b)
                                   for k, b in
                                   self._stats_base.items()}})
            print(f"[bigdl_tpu.serving] engine {self.service_name!r} "
                  f"crashed: {e!r}; postmortem -> {path}",
                  file=sys.stderr)
        except Exception as pe:
            print(f"[bigdl_tpu.serving] postmortem write failed: "
                  f"{pe!r} (crash: {e!r})", file=sys.stderr)

    def _process_triggers(self, occupied: List[int],
                          advanced: List[int]) -> None:
        """Once-per-iteration incident funnel: drain detector
        triggers recorded on the sampler thread, feed the
        iteration-scale stall detector (a live slot that stops
        advancing — sampler cadence is far too coarse for that), and
        map active watchdog alerts (plus a chaos-forced burn, which
        mints no real watchdog alert) onto the same stream. Every
        surviving trigger becomes one capture attempt, deduped by the
        manager's per-kind cooldown. Host-side bookkeeping only."""
        now = time.monotonic()
        triggers = self._bank.drain()
        triggers += self._bank.observe_iteration(now, occupied,
                                                 advanced)
        alerts = self.alerts()
        if self._chaos is not None and self._chaos.burn_active():
            alerts = alerts + [{"alert": "slo:forced_burn",
                                "severity": "critical",
                                "forced": True}]
        triggers += self._bank.alert_triggers(alerts, now)
        for t in triggers:
            name = str(t.get("detector", "detector"))
            c = self._trig_counters.get(name)
            if c is None:
                c = self._inc_ins.triggers_total.labels(
                    self.service_name, name)
                self._trig_counters[name] = c
            c.inc()
            self._capture_incident(t)
        for name, state in self._bank.states().items():
            g = self._det_gauges.get(name)
            if g is None:
                g = self._inc_ins.detector_state.labels(
                    self.service_name, name)
                self._det_gauges[name] = g
            g.set(1.0 if state == "firing" else 0.0)

    def _capture_incident(self, trigger: dict,
                          error: Optional[BaseException] = None):
        """Hand one trigger to the incident manager with the live
        evidence: the finished-timeline ring (exemplar source), the
        qos/latency/cost/loop stats blocks, and the memory/page-pool
        picture. Best-effort — capture must never take down the loop
        (or the crash path, which also funnels through here)."""
        try:
            with self._timelines_lock:
                tls = list(self._timelines)
            stats = {
                "qos": self._qos_summary(),
                "latency": self._latency_summary(),
                "cost": self._cost.summary(),
                "loop": self._loop_obs.summary(),
                "queue_depth": len(self._queue),
                "active_slots": sum(s is not None
                                    for s in self._slots),
                "jit_compiles": self._compile_total(),
            }
            memory = {"pools": self._pool_bytes,
                      "paging": self._paging_summary()}
            return self._incidents.capture(
                trigger, timelines=tls, stats=stats, memory=memory,
                error=error)
        except Exception:
            return None

    def _iterate(self) -> bool:
        """One turn of the loop, as child spans of ``serving/iteration``
        whose boundaries touch. The spans are the loop's one timing
        source: the phase accumulator takes its seconds from them (a
        dispatch span's duration, reported where it closes; the SELF
        time of ``admission`` and ``deliver``, whose dispatches are
        their children), so phase seconds sum to the iteration's wall
        by construction."""
        lo = self._loop_obs
        with trace.span("serving/sweep") as sp:
            now = self._sweep()
        lo.add("sweep", sp.duration)

        # 3. admission: prefix-aware intake + batched chunked-prefill
        #    rounds under this iteration's budget — every round
        #    advances ALL staged admissions together through one
        #    ragged dispatch
        worked = False
        with trace.span("serving/admission") as sp:
            self._policy.begin_iteration()
            while True:
                self._fill_admissions(now)
                if not self._adms or not self._policy.take_chunk():
                    break
                self._prefill_round()
                worked = True
        lo.add("admission", sp.self_ns() / 1e9)

        # 4. one fused decode step over every occupied slot; what is
        #    left of the span beside the dispatch is sampling transfers
        #    and stream delivery (the "deliver" bubble)
        with trace.span("serving/deliver") as sp:
            occupied = [sid for sid, st in enumerate(self._slots)
                        if st is not None]
            active = list(occupied)
            if self._chaos is not None:
                # frozen slots sit out this round's fused step (their
                # KV and handle are untouched — they resume when the
                # freeze expires), simulating a straggler row
                active = [sid for sid in active
                          if not self._chaos.slot_frozen(sid)]
            if active:
                self._decode_all(active)
                worked = True
        lo.add("deliver", sp.self_ns() / 1e9)

        with trace.span("serving/observe") as sp:
            self._observe(occupied, active)
        lo.add("observe", sp.duration)
        return worked

    def _sweep(self) -> float:
        """Cancellation and deadline eviction over running slots,
        admissions in progress and the queue. Returns the iteration's
        ``now`` (monotonic)."""
        now = time.monotonic()
        # a fresh iteration may admit again — pages freed by the
        # releases/donations since can satisfy what blocked before
        self._adm_blocked = False
        if self._chaos is not None:
            self._chaos.begin_iteration()

        # 1. running slots: cancellation + deadline eviction
        for sid, st in enumerate(self._slots):
            if st is None:
                continue
            h = st.handle
            if h.cancelled:
                self._release(sid, RequestCancelled(
                    f"cancelled after {st.delivered} tokens"),
                    "cancelled")
            elif h.deadline is not None and now > h.deadline:
                self._release(sid, RequestTimedOut(
                    f"deadline passed mid-decode after {st.delivered} "
                    "tokens (partial output in tokens_so_far())"),
                    "timed_out")
        # ... and the admissions in progress
        for a in list(self._adms):
            h = a.handle
            err = kind = None
            if h.cancelled:
                err, kind = RequestCancelled(
                    "cancelled during prefill"), "cancelled"
            elif h.deadline is not None and now > h.deadline:
                err, kind = RequestTimedOut(
                    "deadline passed during prefill"), "timed_out"
            if err is not None:
                self._abort_admission(a, err, kind)

        # 2. queued requests: mid-queue deadline/cancel sweep
        for h, err in self._queue.sweep(now):
            self._finish_dropped(h, err)
        return now

    def _observe(self, occupied: List[int], active: List[int]) -> None:
        """5. load gauges + watchdog sampling (one probe read and one
        histogram snapshot per objective — iteration-rate cheap), and
        the phase counters' flush: what the telemetry itself costs on
        the decode thread."""
        lo = self._loop_obs
        ins = self._ins
        ins.active_slots.set(sum(s is not None for s in self._slots))
        ins.queue_depth.set(len(self._queue))
        ins.jit_compiles.set(self._compile_total())
        self._accrue_kv()
        self._sync_page_gauges()
        self._recompile_wd.sample()
        self._slo_wd.sample()
        self._slo_budget.sample(
            forced=self._chaos is not None
            and self._chaos.burn_active())
        self._process_triggers(occupied, active)
        mfu_d, bw_d = self._cost.rates("decode")
        if mfu_d is not None:
            ins.mfu_decode.set(mfu_d)
        if bw_d is not None:
            ins.membw_util_decode.set(bw_d)
        mfu_p, bw_p = self._cost.rates("prefill")
        if mfu_p is not None:
            ins.mfu_prefill.set(mfu_p)
        if bw_p is not None:
            ins.membw_util_prefill.set(bw_p)
        lo.iteration()
        # the counters trail the accumulator by this span's own seconds,
        # which the next iteration's flush carries
        snap = lo.summary()
        for p, child in self._loop_phase_counters.items():
            delta = snap["phases"][p] - self._loop_flushed[p]
            if delta > 0.0:
                child.inc(delta)
                self._loop_flushed[p] += delta
        ins.loop_idle_fraction.set(snap["device_idle_fraction"])

    # ------------------------------------------------ admission stages
    def _free_slot(self) -> Optional[int]:
        # a slot is free when no running request occupies it AND no
        # in-flight admission has reserved it as its insert target
        reserved = {a.slot for a in self._adms}
        for sid, st in enumerate(self._slots):
            if st is None and sid not in reserved:
                return sid
        return None

    # ------------------------------------------------------ preemption
    def _maybe_preempt(self, now: float) -> bool:
        """With the slot pool exhausted and a high-class request
        waiting past ``preempt_slack_s``, evict one lower-class slot:
        lowest class first, longest-remaining-work tie-break (the
        victim with the most decode left ahead of it loses the least
        sunk progress per unit of freed time). The victim's KV is
        donated to the prefix index and PINNED, the request requeued
        at the queue head — its automatic re-admission re-prefills
        only the tail the donated entry doesn't cover and resumes
        token-identical. High-class slots are never preempted; a pool
        full of high is simply full. Returns True when a slot was
        freed."""
        if self.preempt_slack_s is None:
            return False
        wait = self._queue.oldest_waiting("high", now)
        if wait is None or wait <= self.preempt_slack_s:
            return False
        victim_sid, victim_key = None, None
        for sid, st in enumerate(self._slots):
            if st is None:
                continue
            rank = PRIORITY_RANK.get(st.handle.priority, 1)
            if rank <= 0:
                continue  # never preempt a high-class slot
            remaining = st.handle.max_new_tokens - st.delivered
            key = (rank, remaining)
            if victim_key is None or key > victim_key:
                victim_sid, victim_key = sid, key
        if victim_sid is None:
            return False
        self._preempt_slot(victim_sid, now)
        return True

    def _preempt_slot(self, sid: int, now: float) -> None:
        st = self._slots[sid]
        h = st.handle
        # the slot's KV covers [0, pos): prompt + generated[:-1] —
        # exactly the donation key a finishing slot would use
        tokens = np.concatenate(
            [h.prompt, np.asarray(h._tokens[:-1], np.int32)])
        self._maybe_donate(sid, tokens, h.request_id, st.snaps,
                           st.prompt_entry)
        if self._prefix is not None:
            # pin the covering entry so the LRU cannot evict the
            # donated KV while the victim waits in the queue — the
            # pin is released at re-admission (or by _finish_handle
            # if the victim times out / is cancelled first). The
            # donation may have been declined (covered / all-pinned):
            # pin whatever entry covers the tokens, if any — a None
            # pin just means the resume re-prefills from scratch,
            # which is still token-identical.
            pin = self._prefix.pin_covering(tokens)
            if pin is not None:
                stale = h.__dict__.pop("_preempt_pin", None)
                if stale is not None:
                    self._prefix.release(stale)
                h._preempt_pin = pin
        self._release_snaps(st.snaps)
        self._free_slot_table(sid)
        self._slots[sid] = None
        self._ins.evicted_total.inc()
        h.preempted += 1
        rec = getattr(h, "_usage", None)
        if rec is not None:
            # slot residency closes into kv_byte_seconds and the
            # requeue stamp opens a second queue-wait segment;
            # device-seconds already charged stay charged (the work
            # happened) — NOT a terminal transition
            self._usage.preempted(rec, now)
        self._qos_counts["preempted"] += 1
        self._qos_ins.preempted_total.labels(
            self.service_name, h.priority,
            getattr(h, "tenant", None) or "unknown").inc()
        self._rec.record("request/preempted", h.request_id,
                         service=self.service_name, slot=sid,
                         priority=h.priority, preempted=h.preempted,
                         tokens_so_far=len(h._tokens),
                         donated_tokens=int(tokens.shape[0]))
        self._queue.requeue(h)

    def _fill_admissions(self, now: float) -> None:
        """Start new admissions until every prefill-dispatch row is
        taken, the slots or the page pool are exhausted, or the queue
        runs dry. With a prefix cache and ``admission_window > 1``, the
        pop prefers the queued candidate with the longest cached prefix
        that the pool can still hold (bounded bypass — see
        AdmissionQueue.pop_ready)."""
        if self._adm_blocked:
            # the pool already refused this iteration's queue head —
            # popping more candidates would just thrash requeues
            return
        scorer = None
        if self._prefix is not None and self.admission_window > 1:
            c, ps = self._policy.chunk, self.page_size

            def scorer(h):
                # score by the USABLE (capped, chunk-aligned) reuse —
                # exactly what _start_admission will skip — so a match
                # that alignment reduces to zero never bypasses the
                # FCFS head for nothing; a candidate whose FRESH page
                # need exceeds what the pool could cover even after a
                # full prefix reclaim scores negative by the shortfall
                # — electing it would stall the fill loop for nothing.
                # The raw lookup is stamped on the handle
                # (generation-guarded) so the winner's admission
                # doesn't re-walk the trie. Preempted requests score
                # by their EFFECTIVE prompt (prompt + already-generated
                # tokens) — the donated KV makes them near-perfect
                # hits.
                p = self._effective_prompt(h)
                found = self._prefix.match(p)
                e, m = found.entry, found.length
                h._prefix_probe = (found, self._prefix.generation)
                base = (min(m, p.shape[0] - 1) // c) * c
                if e is not None and e.tier != "device":
                    base = 0  # promote may still land it, score cold
                g = (self._spec.gamma if self._spec is not None
                     else 0)
                need = pages_needed(
                    min(p.shape[0] + h.max_new_tokens + g,
                        self._phys_len), ps)
                fresh = need - base // ps
                avail = (self._pages.free_pages
                         + self._prefix.device_pages)
                return page_fit_score(base, fresh, avail)
        while len(self._adms) < self._policy.prefill_rows:
            slot = self._free_slot()
            if slot is None:
                # slot pool exhausted: a high-class request waiting
                # past its slack may preempt a lower-class victim
                # (KV donated, victim requeued — see _maybe_preempt)
                if not self._maybe_preempt(now):
                    return
                slot = self._free_slot()
                if slot is None:
                    return
            used = {a.row for a in self._adms}
            row = next(r for r in range(self._policy.prefill_rows)
                       if r not in used)
            h, dropped = self._queue.pop_ready(
                now, scorer=scorer, window=self.admission_window)
            for hd, err in dropped:
                self._finish_dropped(hd, err)
            if h is None:
                return
            if not self._start_admission(h, slot, row):
                return

    @staticmethod
    def _effective_prompt(h: RequestHandle) -> np.ndarray:
        """What a (re)admission must have in the KV cache before
        decode can continue: the prompt plus every already-generated
        token. Fresh requests: just the prompt. Preempted requests:
        the tail token's KV was never written (variable-advance
        invariant), but its position must still be COMPUTED — its
        logits seed the next token — so the full generated list is
        part of the effective prompt and the re-prefill covers
        exactly the suffix the donated entry doesn't."""
        if h._tokens:
            return np.concatenate(
                [h.prompt, np.asarray(h._tokens, np.int32)])
        return h.prompt

    def _start_admission(self, h: RequestHandle, slot: int,
                         row: int) -> bool:
        """Start one popped request's chunked prefill: reserve its
        FULL page span up front — shared prefix head by refcount bump, fresh tail from
        the free list (with a reclaim sweep of unpinned prefix entries
        under pressure) — and never copy a row. A hit's shared pages
        are READ through the block table while the prefill writes land
        only in the fresh tail (chunk alignment implies page
        alignment, so a shared page is never written): the zero-copy
        hit leg. Admission is the ONLY allocation point — the
        reservation covers prompt + max_new_tokens (+ gamma verify
        headroom), so decode can never run out of pages mid-flight
        and ``ensure_writable`` never fires on an engine path.

        Returns False when the pool cannot cover the reservation even
        after reclaim: the request goes back to the queue HEAD (its
        order is preserved) and the ``_adm_blocked`` latch stops the
        fill loop for this iteration — pages free as slots finish, so
        the next iteration retries instead of thrashing pop/requeue."""
        c, ps = self._policy.chunk, self.page_size
        prompt = self._effective_prompt(h)
        t0 = prompt.shape[0]
        base, entry, from_host = 0, None, False
        shortfall, resume_sid = 0, None
        if self._prefix is not None:
            # reuse the pop_ready scorer's lookup when it is still
            # valid — the generation guard rejects probes that predate
            # any donation/eviction/tier move
            probe = h.__dict__.pop("_prefix_probe", None)
            if probe is not None \
                    and probe[1] == self._prefix.generation:
                found = probe[0]
            else:
                found = self._prefix.match(prompt)
            e, matched = found.entry, found.length
            if e is not None:
                # cap at t0-1 (last position must be COMPUTED), then
                # chunk-align DOWN — and c % page_size == 0 makes the
                # reuse base page-aligned, the COW-free invariant
                base = (min(matched, t0 - 1) // c) * c
                if self._lane_state:
                    # the match is already cut to the deepest snapshot
                    # (a multiple of the stride, so of the chunk); what
                    # the pages alone would have covered beyond it is
                    # prefilled again
                    resume_sid = e.resume_at(base)[1]
                    shortfall = (min(found.matched, t0 - 1) // c) * c \
                        - base
            from_host = base > 0 and e.tier == "host"
            if from_host and not self._promote_entry(e):
                # the host pages could not be made device-resident
                # (pool exhausted, or the buffer raced away) — a CLEAN
                # miss, never a read of uninitialized pages
                base, e = 0, None
                from_host = False
            if base > 0:
                entry = e
        shared = (tuple(entry.pages[:base // ps])
                  if entry is not None else ())
        g = self._spec.gamma if self._spec is not None else 0
        remaining = h.max_new_tokens - len(h._tokens)
        need_tokens = min(t0 + remaining + g, self._phys_len)
        n_fresh = pages_needed(need_tokens, ps) - len(shared)
        if resume_sid is not None:
            # the admission's own reference, taken BEFORE any sweep
            # (whose victim may be the hit's own entry, which would
            # free the snapshot with it): the snapshot outlives its
            # entry's eviction while this request prefills, and follows
            # the request into the entry it donates
            self._snaps.share([resume_sid])
        table = BlockTable.build(self._pages, shared, n_fresh)
        if table is None and self._prefix is not None:
            # hold the hit's head across the sweep: its own entry may
            # be the victim, and a page survives its entry only while
            # something else references it
            self._pages.share(shared)
            self._prefix.reclaim(n_fresh, self._spill_pages)
            table = BlockTable.build(self._pages, shared, n_fresh)
            self._pages.free(shared)
        d_table = None
        if table is not None and self.draft is not None:
            # the draft pool is sized so a draft reservation can never
            # fail once the target's succeeded (1 + max_slots *
            # table_len, no prefix sharing) — the unwind is belt and
            # braces for exotic subclassing
            d_table = BlockTable.build(
                self._d_pages, (),
                pages_needed(need_tokens, ps))
            if d_table is None:
                table.free()
                table = None
        if table is None:
            if resume_sid is not None:
                self._snaps.free([resume_sid])
            self._queue.requeue(h)
            self._adm_blocked = True
            # sticky per-request latch: the finished timeline reports
            # page_waited and the incident exemplars classify the
            # request page_wait-bound
            h._page_waited = True
            self._rec.record("request/page_wait", h.request_id,
                             service=self.service_name,
                             needed_pages=n_fresh,
                             free_pages=self._pages.free_pages)
            return False
        if self._prefix is not None:
            if shortfall:
                self._prefix.record_shortfall(shortfall)
                self._ins.state_hits_shortened_total.inc()
            if base > 0:
                # no copy and no entry acquire: the shared
                # refcounts keep the pages alive even if the entry is
                # evicted while we prefill (single mutator thread)
                self._prefix.record_hit(entry, base, host=from_host)
                self._ins.prefix_hits_total.inc()
                if from_host:
                    self._ins.prefix_host_hits_total.inc()
                    self._sync_prefix_gauges()
                self._ins.prefix_reused_tokens_total.inc(base)
                self._rec.record("request/prefix_hit", h.request_id,
                                 service=self.service_name,
                                 matched_tokens=base,
                                 tail_tokens=t0 - base,
                                 shared_pages=len(shared),
                                 tier="host" if from_host
                                 else "device")
            else:
                self._prefix.record_miss()
                self._ins.prefix_misses_total.inc()
            # the preemption-time pin held the donated entry alive
            # across the queue wait; the admission has now taken its
            # own page references (or cleanly missed) — the insurance
            # ref can go
            pin = h.__dict__.pop("_preempt_pin", None)
            if pin is not None:
                self._prefix.release(pin)
        tail = t0 - base
        n_chunks = self._policy.n_chunks(tail)
        ids = np.zeros((n_chunks * c,), np.int32)  # right-pad final chunk
        ids[:tail] = prompt[base:]
        d_ids, d_n_chunks = None, 0
        if self.draft is not None:
            # the draft prefills the FULL prompt — the prefix index
            # holds target KV only, so a hit skips target chunks but
            # never draft chunks
            d_n_chunks = self._policy.n_chunks(t0)
            d_ids = np.zeros((d_n_chunks * c,), np.int32)
            d_ids[:t0] = prompt
        adm = _Admission(h, slot, row, ids, t0, base, n_chunks, table,
                         d_ids, d_n_chunks, d_table)
        self._adms.append(adm)
        if self._lane_state:
            adm.keep_at = base + shortfall \
                - (base + shortfall) % self._snap_stride
        if resume_sid is not None or shortfall:
            self._restore_state(adm, resume_sid, shortfall)
        h.prefix_tokens = base
        t_adm = time.monotonic()
        if h.admitted_at is None:
            # set-once: a preempted request keeps its ORIGINAL
            # admission stamp — first_token_at is set-once too, so a
            # re-stamp would turn the timeline's prefill_s negative
            h.admitted_at = t_adm
        rec = getattr(h, "_usage", None)
        if rec is not None:
            # queue wait closes (re-admissions ACCUMULATE from the
            # requeue stamp) and the chunk-aligned reuse is credited
            # as tokens + bytes saved
            self._usage.admitted(rec, t_adm, reused_tokens=base)
        self._rec.record("request/admitted", h.request_id,
                         service=self.service_name, slot=slot,
                         staging_row=row, n_chunks=n_chunks,
                         prefix_tokens=base, pages=len(table),
                         shared_pages=len(shared))
        self._ins.admitted_total.inc()
        return True

    def _prefill_round(self) -> None:
        """Advance EVERY in-flight admission by one chunk through one
        ragged dispatch — plus, with a draft, one MIRRORED ragged
        dispatch over the draft pool — then complete the ones whose
        prompt is fully written in every pool that needs it (table
        handoff + first-token sample).

        A prefix-cache hit can leave the target cursor finished while
        the draft still prefills the reused head: those rows REPLAY
        their final target chunk each round (an idempotent rewrite —
        same ids, same offset, same KV values) so the fixed-shape
        dispatch needs no per-row liveness flag and the final-round
        logits are fresh for the first-token sample whenever the
        admission actually completes."""
        c = self._policy.chunk
        rows = self._policy.prefill_rows
        spec = self.draft is not None
        ids = np.zeros((rows, c), np.int32)
        pos0 = np.zeros((rows,), np.int32)
        last = np.full((rows,), c - 1, np.int32)
        finals: List[_Admission] = []
        for a in self._adms:
            # once the target cursor is past its last chunk (draft
            # still catching up), clamp to the final chunk: a replay
            k = min(a.next_chunk, a.n_chunks - 1)
            ids[a.row] = a.ids[k * c:(k + 1) * c]
            pos0[a.row] = a.base + k * c
            if a.next_chunk >= a.n_chunks - 1:
                # the true last prompt position within the final chunk
                # — pad positions behind it are written but never
                # attended (causal mask within the chunk; decode
                # overwrites position p before attending <= p)
                last[a.row] = a.tail - 1 - (a.n_chunks - 1) * c
                if not spec or a.d_next_chunk >= a.d_n_chunks - 1:
                    finals.append(a)
        # a COLD dispatch's wall is dominated by its one-time compile —
        # billing that to whichever tenants happen to arrive first
        # would poison their device-seconds forever, so warmup rounds
        # are excluded from attribution AND the busy tally (both sides
        # skip: conservation holds, goodput reads the warm engine)
        was_warm = "chunk" in self._warm and (
            not spec or "d_chunk" in self._warm) and (
            not finals or "sample0" in self._warm)
        # pro-rata attribution by REAL tokens each row advanced (the
        # padded tail of a final chunk is engine overhead, not billable
        # work; a replayed chunk advances nothing and earns nothing;
        # draft chunks are real mirrored work); weights sum to 1 — the
        # round's full wall is conserved
        done_by = []
        for a in self._adms:
            t_done = (min(c, a.tail - a.next_chunk * c)
                      if a.next_chunk < a.n_chunks else 0)
            d_done = min(c, a.t0 - a.d_next_chunk * c) if spec else 0
            done_by.append((a, t_done, d_done))
        if self._chaos is not None:
            self._chaos.on_dispatch()
        with trace.span(
                "serving/prefill_dispatch", rows=len(self._adms),
                tokens=sum(t + d for _, t, d in done_by),
                request_ids=[a.handle.request_id for a in self._adms],
                **self._chunk_read([pos0[a.row] for a in self._adms],
                                   c)) as disp:
            # each row writes through its admission's reserved block
            # table (idle rows carry the all-scratch table — their
            # padding writes hit page 0)
            logits, self._kv_pool = self._chunk_jit(
                self._params, self._buffers, self._h2d(ids),
                self._kv_pool, self._adm_tables(), self._h2d(pos0),
                self._h2d(last), *self._adm_lanes())
            self._warm.add("chunk")
            if self._lane_state:
                self._take_snapshots(c)
            if spec:
                d_ids = np.zeros((rows, c), np.int32)
                d_pos0 = np.zeros((rows,), np.int32)
                for a in self._adms:
                    dk = a.d_next_chunk
                    d_ids[a.row] = a.d_ids[dk * c:(dk + 1) * c]
                    d_pos0[a.row] = dk * c
                _, self._d_kv_pool = self._d_chunk_jit(
                    self._d_params, self._d_bufs, self._h2d(d_ids),
                    self._d_kv_pool, self._adm_tables(draft=True),
                    self._h2d(d_pos0),
                    self._h2d(np.zeros((rows,), np.int32)))
                self._warm.add("d_chunk")
            toks = None
            if finals:
                # the host-side transfer blocks on the sampled tokens —
                # which depend on the chunk's logits, so the measured wall
                # covers the real dispatch on rounds that finish a prompt
                toks = np.asarray(self._sample0_jit(
                    logits, self._next_key(), self._temp()))
                self._warm.add("sample0")
        # the span's warm-only wall feeds the usage ledger, the cost
        # model, and the loop-phase busy pool — one measurement, three
        # views, so roofline/idle/goodput figures reconcile exactly
        wall = disp.duration
        self._loop_obs.dispatch("prefill_dispatch", wall, warm=was_warm)
        self._cost.charge("prefill", wall, warm=was_warm)
        if was_warm:
            total_done = sum(t + d for _, t, d in done_by) or 1
            self._usage.charge_dispatch(
                "prefill", wall,
                [(getattr(a.handle, "_usage", None),
                  (t + d) / total_done)
                 for a, t, d in done_by],
                rows_advanced=len(self._adms),
                capacity_rows=self._policy.prefill_rows)
        for a, t_done, d_done in done_by:
            if t_done:
                k = a.next_chunk
                # only TARGET prompt tokens count as prefill work —
                # draft mirroring is engine overhead, and the billing
                # invariant prefill + prefix_reused == prompt holds
                self._prefilled_tokens += t_done
                self._ins.prefill_tokens_total.inc(t_done)
                rec = getattr(a.handle, "_usage", None)
                if rec is not None:
                    self._usage.add_prefill(rec, t_done)
                self._rec.record("request/prefill_chunk",
                                 a.handle.request_id,
                                 service=self.service_name, chunk=k,
                                 n_chunks=a.n_chunks, tokens=t_done)
                a.next_chunk += 1
            if spec:
                a.d_next_chunk += 1
        for a in finals:
            self._complete_admission(a, int(toks[a.row]))

    def _complete_admission(self, a: _Admission, tok: int) -> None:
        # zero-copy handoff: the admission's reserved tables BECOME
        # the slot's — the pages already hold the prompt KV
        self._free_slot_table(a.slot)
        self._tables[a.slot] = a.table
        a.table = None
        held_snaps, a.snaps = a.snaps, []
        if self.draft is not None:
            self._d_tables[a.slot] = a.d_table
            a.d_table = None
        self._adms.remove(a)
        now = time.monotonic()
        h = a.handle
        first = h.first_token_at is None
        h._deliver(tok, now)
        rec = getattr(h, "_usage", None)
        if rec is not None:
            self._usage.delivered(rec, 1)
        if first:
            # re-admissions of a preempted request deliver here too,
            # but their first token shipped long ago — observing a
            # second TTFT would double-count the request
            self._ins.ttft_seconds.observe(now - h.submitted_at)
            # the histograms carry no priority label, so the budget
            # ledger's per-class view is fed directly at the source
            self._slo_budget.observe_class(
                getattr(h, "priority", "normal") or "normal",
                now - h.submitted_at)
            self._rec.record("request/first_token", h.request_id,
                             service=self.service_name, token=tok,
                             ttft_s=now - h.submitted_at)
        else:
            self._rec.record("request/resumed", h.request_id,
                             service=self.service_name, slot=a.slot,
                             tokens_so_far=len(h._tokens),
                             prefix_tokens=a.base,
                             reprefilled_tokens=a.t0 - a.base)
        if (self.eos_id is not None and tok == self.eos_id) \
                or len(h._tokens) >= h.max_new_tokens:
            # instant finisher: the slot's pages hold the effective
            # prompt's KV — donate them before the slot identity is
            # lost (prompt + generated[:-1] is exactly what they cover)
            self._maybe_donate(a.slot, np.concatenate(
                [h.prompt, np.asarray(h._tokens[:-1], np.int32)]),
                h.request_id, held_snaps)
            self._release_snaps(held_snaps)
            self._free_slot_table(a.slot)
            self._finish_handle(h, None, "finished")
            self._ins.finished_total.inc()
            return
        st = _SlotState(h, a.t0, tok, now)
        st.snaps = held_snaps
        if self._donate_at_prefill_end:
            # the prompt's pages are complete (decode writes past them):
            # a request for the same prefix that arrives while this one
            # decodes is a hit and does not prefill it again
            st.prompt_entry = self._maybe_donate(
                a.slot, np.concatenate(
                    [h.prompt, np.asarray(h._tokens[:-1], np.int32)]),
                h.request_id, held_snaps)
        # a resumed request's slot picks up where the preempted one
        # left off: pos == effective-prompt length keeps the
        # variable-advance invariant (KV covers [0, pos), the just-
        # delivered token's KV unwritten) for fresh and resumed alike
        st.delivered = len(h._tokens)
        self._slots[a.slot] = st

    def _free_admission_tables(self, a: _Admission) -> None:
        self._release_snaps(a.snaps)
        a.snaps = []
        if a.table is not None:
            a.table.free()
            a.table = None
        if a.d_table is not None:
            a.d_table.free()
            a.d_table = None

    def _abort_admission(self, a: _Admission, err: Exception,
                         kind: str) -> None:
        self._free_admission_tables(a)
        self._adms.remove(a)
        self._count_drop(kind)
        self._finish_handle(a.handle, err, kind)

    # --------------------------------------------------- prefix donation
    def _maybe_donate(self, sid: int, tokens: np.ndarray,
                      request_id: str, snaps=(), supersede=None):
        """Offer a slot's KV to the prefix index: when its prompt's
        prefill ends, and again (``supersede`` the entry of the first
        time) when the slot is given up. ``tokens`` are exactly the ids
        whose KV the slot holds (positions ``0..len-1``); the index
        decides (covered / LRU-evict / decline). Donation is a refcount
        move, never a copy: the covering pages are SHARED into the new
        entry; the slot's own references are freed separately by the
        caller. ``snaps`` (a model with lane state): the request's
        state snapshots, shared into the entry the same way. Returns
        the entry, None where none was made."""
        if self._prefix is None:
            return None
        tbl = self._tables[sid]
        entry = None
        if tbl is not None and tokens.shape[0] > 0:
            held = tbl.covering(int(tokens.shape[0]))
            entry = self._prefix.donate_pages(tokens, held, snaps,
                                              supersede)
            if entry is not None:
                self._rec.record(
                    "request/prefix_donated", request_id,
                    service=self.service_name,
                    tokens=int(tokens.shape[0]), pages=len(held))
        self._sync_prefix_gauges()
        return entry

    def _sync_prefix_gauges(self) -> None:
        """Publish the prefix cache's flow deltas and occupancy, both
        tiers (device pages + host spill)."""
        ev = self._prefix.evictions
        if ev > self._prefix_evictions_seen:
            self._ins.prefix_evicted_total.inc(
                ev - self._prefix_evictions_seen)
            self._prefix_evictions_seen = ev
        self._ins.prefix_cache_bytes.set(self._prefix.bytes_in_use)
        self._ins.prefix_cache_entries.set(len(self._prefix))
        if self._prefix.host_pages > 0:
            dm = self._prefix.demotions
            if dm > self._prefix_demotions_seen:
                self._ins.prefix_host_demoted_total.inc(
                    dm - self._prefix_demotions_seen)
                self._prefix_demotions_seen = dm
            hev = self._prefix.host_evictions
            if hev > self._prefix_host_evictions_seen:
                self._ins.prefix_host_evicted_total.inc(
                    hev - self._prefix_host_evictions_seen)
                self._prefix_host_evictions_seen = hev
            self._ins.prefix_host_cache_bytes.set(
                self._prefix.host_bytes_in_use)
            self._ins.prefix_host_cache_entries.set(
                self._prefix.stats()["host_entries"])

    # ------------------------------------------------ host-tier moves
    def _promote_entry(self, entry) -> bool:
        """Make a host-tier entry device-resident for the admission
        consuming it, synchronously: allocate fresh pages (reclaim
        sweep of unpinned prefix entries under pressure), land each
        host page buffer with the warmed per-page transfer + scatter,
        flip the entry's tier. False = clean miss (pool exhausted or
        the buffer raced away)."""
        if entry.tier != "host":
            return entry.tier == "device"
        buf = entry.host_buf
        if buf is None:
            return False  # spill still pending or already evicted
        n = len(buf)
        pages = self._pages.alloc(n)
        if pages is None:
            self._prefix.reclaim(n, self._spill_pages)
            pages = self._pages.alloc(n)
        if pages is None:
            return False
        from bigdl_tpu.parallel.tp import put_from_host

        try:
            for dst, host_page in zip(pages, buf):
                one = put_from_host(host_page, self._kv_shard)
                self._kv_pool = self._copy_row_jit(
                    self._kv_pool, one, jnp.int32(dst), jnp.int32(0))
            self._warm.add("copy:promote")
        except Exception:
            self._pages.free(pages)
            return False
        self._prefix.promote_pages(entry, pages)
        self._ins.prefix_host_promoted_total.inc()
        return True

    def _spill_pages(self, pages):
        """Demotion spill callback for ``PagedPrefixIndex.reclaim``:
        lift each victim page out of the pool with the warmed slice
        and bulk-copy it host-side. Returns the per-page host buffer
        list the host tier retains, or None to degrade the demotion
        to a plain drop (the index never keeps an entry pointing at
        garbage)."""
        if self._take_row_jit is None:
            return None
        from bigdl_tpu.parallel.tp import fetch_to_host

        try:
            out = []
            for p in pages:
                one = self._take_row_jit(self._kv_pool, jnp.int32(p))
                out.append(fetch_to_host(one))
            self._warm.add("copy:demote")
            return out
        except Exception:
            return None

    # ---------------------------------------------------- page plumbing
    def _copy_page(self, dst: int, src: int) -> None:
        """``BlockTable.ensure_writable``'s copy callback: one warmed
        jitted single-page copy inside the target pool. Engine hot
        paths never trigger COW (full-span reservation at admission);
        this exists for API users forking tables (n>1 completions)."""
        self._kv_pool = self._copy_page_jit(
            self._kv_pool, jnp.int32(dst), jnp.int32(src))

    def _adm_tables(self, draft: bool = False):
        """The prefill dispatch's ``(prefill_rows, table_len)`` block
        tables: each admission row's reserved table, idle rows padded
        with the all-scratch table (their padding writes land on page
        0 and are never attended)."""
        rows = self._policy.prefill_rows
        t = np.zeros((rows, self._table_len), np.int32)
        for a in self._adms:
            tbl = a.d_table if draft else a.table
            if tbl is not None:
                t[a.row] = tbl.as_array(self._table_len)
        return self._h2d(t)

    def _adm_lanes(self) -> tuple:
        """The prefill dispatch's extra argument for a model with lane
        state: the lane (= reserved slot) each row's recurrent state
        lives in, the scratch lane for idle rows. Empty otherwise."""
        if not self._lane_state:
            return ()
        lanes = np.full((self._policy.prefill_rows,), self.max_slots,
                        np.int32)
        for a in self._adms:
            lanes[a.row] = a.slot
        return (self._h2d(lanes),)

    # ------------------------------------------------ lane-state copies
    def _restore_state(self, a: _Admission, snap: Optional[int],
                       shortfall: int) -> None:
        """Admission on a hit: snapshot ``snap`` becomes the state of
        the admission's lane (one warmed copy, dispatched and not
        waited for), and the admission keeps its reference. The span
        also records a match NO snapshot stood under (``snap`` None,
        ``bytes`` 0: nothing is copied, all of the match is prefilled
        again), so that its attributes sum to every matched token."""
        copied = 0 if snap is None else self._snaps.snapshot_bytes
        with trace.span("serving/state_restore",
                        matched_tokens=a.base + shortfall,
                        resumed_tokens=a.base, bytes=copied):
            if snap is None:
                return
            self._kv_pool = self._restore_jit(
                self._kv_pool, self._snap_store, jnp.int32(a.slot),
                jnp.int32(snap))
        a.snaps.append((a.base, snap))
        self._snaps.touch(snap)
        self._ins.state_restored_total.inc()

    def _take_snapshots(self, c: int) -> None:
        """After a prefill dispatch: every row whose chunk was whole
        and ended on a multiple of the stride has its lane's state
        copied into a free snapshot (the dispatch that wrote the state
        is ahead of the copy on the device's queue). A full store first
        gives up the snapshot nothing has resumed from for longest
        (``PagedPrefixIndex.reclaim_snapshot``); failing that the
        boundary goes without one. A request keeps at most three: the
        one it resumed from, the one at the boundary its match named
        (``keep_at``) and the newest; a long cold prompt would
        otherwise fill the store with states nothing will resume from
        before it reaches the boundary others share."""
        for a in self._adms:
            if a.next_chunk >= a.n_chunks:
                continue
            end = a.base + (a.next_chunk + 1) * c
            if end > a.t0 or end % self._snap_stride:
                continue
            snap = self._snaps.take()
            if snap is None and self._prefix is not None \
                    and self._prefix.reclaim_snapshot():
                snap = self._snaps.take()
            if snap is None:
                self._snaps.note_skipped()
                self._ins.state_snapshots_skipped_total.inc()
                continue
            with trace.span("serving/state_snapshot", position=end,
                            bytes=self._snaps.snapshot_bytes):
                self._snap_store = self._snapshot_jit(
                    self._snap_store, self._kv_pool, jnp.int32(snap),
                    jnp.int32(a.slot))
            a.snaps.append((end, snap))
            self._ins.state_snapshots_taken_total.inc()
            keep = {a.base, a.keep_at, end}
            self._release_snaps([p for p in a.snaps if p[0] not in keep])
            a.snaps = [p for p in a.snaps if p[0] in keep]
        self._ins.state_snapshots_in_use.set(self._snaps.in_use)

    def _release_snaps(self, snaps) -> None:
        """Drop a request's own snapshot references (what it donated
        lives on under its entry's)."""
        if snaps:
            self._snaps.free([snap for _, snap in snaps])

    def _slot_tables(self, draft: bool = False):
        """The decode dispatch's ``(max_slots, table_len)`` block
        tables (idle slots all-scratch, same argument as above)."""
        t = np.zeros((self.max_slots, self._table_len), np.int32)
        tables = self._d_tables if draft else self._tables
        for sid, tbl in enumerate(tables):
            if tbl is not None:
                t[sid] = tbl.as_array(self._table_len)
        return self._h2d(t)

    def _free_slot_table(self, sid: int) -> None:
        """Drop slot ``sid``'s page references (target + draft) —
        refcount moves only; pages shared into the prefix index
        survive under the index's references."""
        tbl = self._tables[sid]
        if tbl is not None:
            tbl.free()
            self._tables[sid] = None
        if self._d_tables is not None:
            d = self._d_tables[sid]
            if d is not None:
                d.free()
                self._d_tables[sid] = None

    def _accrue_kv(self) -> None:
        """Per-iteration KV billing: integrate each request's
        ACTUALLY-HELD page bytes over the elapsed interval.
        ``holder_bytes`` prices a shared page pro-rata across its
        refcount, so a page shared by k holders is billed once in
        total no matter how many requests read it — summing every
        holder's accrual can never exceed the pool's physical
        ``bytes_in_use`` integrated over the same window (the
        conservation property the ledger test checks)."""
        now = time.monotonic()
        last, self._last_kv_accrue = self._last_kv_accrue, now
        if last is None:
            return
        dt = now - last
        if dt <= 0.0:
            return

        def bill(h, tbl, d_tbl):
            rec = getattr(h, "_usage", None)
            if rec is None:
                return
            b = (self._pages.holder_bytes(tbl.pages)
                 if tbl is not None else 0.0)
            if d_tbl is not None and self._d_pages is not None:
                b += self._d_pages.holder_bytes(d_tbl.pages)
            if b > 0.0:
                self._usage.accrue_kv(rec, b * dt)

        for sid, st in enumerate(self._slots):
            if st is not None:
                bill(st.handle, self._tables[sid],
                     self._d_tables[sid]
                     if self._d_tables is not None else None)
        for a in self._adms:
            bill(a.handle, a.table, a.d_table)

    def _fragmentation(self) -> float:
        """Internal fragmentation of the live reservations: the token
        slack inside held pages — 1 − covered_tokens / (held_pages ×
        page_size) over every slot table (coverage = the slot's KV
        cursor) and admission table (coverage = reuse base + prefill
        cursor). 0.0 when nothing is held."""
        ps = self.page_size
        c = self._policy.chunk
        held = covered = 0
        for sid, st in enumerate(self._slots):
            tbl = self._tables[sid]
            if st is None or tbl is None:
                continue
            held += len(tbl.pages)
            covered += min(st.pos, len(tbl.pages) * ps)
        for a in self._adms:
            if a.table is None:
                continue
            held += len(a.table.pages)
            covered += min(a.base + a.next_chunk * c, a.t0,
                           len(a.table.pages) * ps)
        if held == 0:
            return 0.0
        return 1.0 - covered / (held * ps)

    def _sync_page_gauges(self) -> None:
        """Publish page-flow counter deltas (target + draft pools
        summed) and pool occupancy/fragmentation gauges."""
        pools = [self._pages]
        if self._d_pages is not None:
            pools.append(self._d_pages)
        stats = [p.stats() for p in pools]
        ins = self._ins
        flows = (("allocated", "allocated_total",
                  ins.page_allocated_total),
                 ("shared", "shared_total", ins.page_shared_total),
                 ("cow_forks", "cow_forks_total",
                  ins.page_cow_forks_total),
                 ("freed", "freed_total", ins.page_freed_total))
        for key, stat_key, counter in flows:
            cur = sum(s[stat_key] for s in stats)
            if cur > self._page_seen[key]:
                counter.inc(cur - self._page_seen[key])
                self._page_seen[key] = cur
        ins.page_pool_bytes.set(
            sum(s["bytes_in_use"] for s in stats))
        ins.page_pool_pages_in_use.set(
            sum(s["pages_in_use"] for s in stats))
        ins.page_pool_fragmentation.set(self._fragmentation())

    def _paging_summary(self) -> dict:
        out = {"page_size": self.page_size,
               "table_len": self._table_len,
               "decode_attention": self._decode_attention,
               "fragmentation": self._fragmentation(),
               "pool": self._pages.stats()}
        if self._chunk_counts is not None:
            out["prefill_kv_read_tokens"] = self._prefill_kv_read
            out["prefill_kv_table_tokens"] = self._prefill_kv_table
        if self._step_counts is not None:
            out["decode_kv_read_tokens"] = self._decode_kv_read
            out["decode_kv_table_tokens"] = self._decode_kv_table
        if self._d_pages is not None:
            out["draft_pool"] = self._d_pages.stats()
        if self._prefix is not None:
            out["prefix_device_pages"] = self._prefix.device_pages
        if self._lane_state:
            out["state"] = {
                "lanes": self.max_slots,
                "snapshot_stride_tokens": self._snap_stride,
                **self._snaps.stats()}
            if self._prefix is not None:
                out["state"]["hits_shortened_total"] = \
                    self._prefix.hits_shortened
                out["state"]["shortfall_tokens_total"] = \
                    self._prefix.shortfall_tokens
        return out

    # --------------------------------------------------------- decode
    def _decode_all(self, active: List[int]) -> None:
        if self.draft is not None:
            return self._decode_all_spec(active)
        tok = np.zeros((self.max_slots,), np.int32)
        pos = np.zeros((self.max_slots,), np.int32)
        for sid in active:
            st = self._slots[sid]
            tok[sid] = st.last_token
            pos[sid] = st.pos
        was_warm = "step" in self._warm   # cold = compile in the wall
        if self._chaos is not None:
            self._chaos.on_dispatch()
        with trace.span("serving/decode_dispatch", rows=len(active),
                        **self._step_read(pos),
                        **self._selected_read(pos[active])) as disp:
            active_arg = ()
            if self._lane_state or self._routed:
                live = np.zeros((self.max_slots,), bool)
                live[active] = True
                active_arg = (self._h2d(live),)
            nxt, self._kv_pool = self._step_jit(
                self._params, self._buffers, self._h2d(tok),
                self._h2d(pos), self._kv_pool, self._slot_tables(),
                self._next_key(), self._temp(), *active_arg)
            self._warm.add("step")
            with trace.span("serving/fetch_tokens"):
                nxt_np = np.asarray(nxt)   # blocks on the fused step
            if self._routed:
                disp.attrs.update(self._routing_read(
                    nxt_np[self.max_slots:]))
        now = time.monotonic()
        # the span's warm-only wall to ledger, cost model, and loop
        # busy — one measurement, three reconciling views
        wall = disp.duration
        self._loop_obs.dispatch("decode_dispatch", wall, warm=was_warm)
        self._cost.charge("decode", wall, warm=was_warm)
        # every advanced row got exactly one token: the step's wall
        # splits evenly across them — identical to weighting by
        # delivered tokens, the speculative path's rule (idle slots
        # ride along as padding — their share is the dispatch's
        # padding waste, not billed). Warmup steps are excluded like
        # cold prefill rounds above.
        if was_warm:
            w = 1.0 / len(active)
            self._usage.charge_dispatch(
                "decode", wall,
                [(getattr(self._slots[sid].handle, "_usage", None), w)
                 for sid in active],
                rows_advanced=len(active), capacity_rows=self.max_slots)
        for sid in active:
            self._deliver_burst(sid, nxt_np[sid:sid + 1], now)

    def _chunk_read(self, pos0, chunk: int) -> dict:
        """Attributes for the prefill span of a model whose chunk attends
        by key blocks (it says so by ``prefill_read_counts``): the
        tokens' worth of table slots a full-attention layer gathers for
        the rows that hold an admission, and what their whole tables
        hold; also summed into ``stats()["paging"]``. Nothing for any
        other model."""
        if self._chunk_counts is None:
            return {}
        read = self._chunk_counts(pos0, chunk, self.page_size,
                                  self._table_len)
        self._prefill_kv_read += read["kv_read_tokens"]
        self._prefill_kv_table += read["kv_table_tokens"]
        return read

    def _step_read(self, pos) -> dict:
        """Attributes for the decode span of a model with full-attention
        layers (it says so by ``step_read_counts``): the tokens' worth
        of pages one such layer's step reads for the dispatch's lanes,
        idle ones included, and what their whole tables hold; also
        summed into ``stats()["paging"]``. Nothing for any other
        model."""
        if self._step_counts is None:
            return {}
        read = self._step_counts(pos, self.page_size, self._table_len,
                                 self._decode_attention)
        self._decode_kv_read += read["kv_read_tokens"]
        self._decode_kv_table += read["kv_table_tokens"]
        return read

    def _selected_read(self, positions) -> dict:
        """Attributes for the decode span of a model some of whose layers
        select what they read (it says so by ``decode_read_counts``):
        what they attended and what the step gathered of what the rows
        hold, reckoned from the rows' positions; nothing for any other
        model."""
        if self._read_counts is None:
            return {}
        read = self._read_counts(positions, self._table_len)
        self._ins.selected_attended_tokens_total.inc(read["attended_tokens"])
        self._ins.selected_gathered_tokens_total.inc(read["gathered_tokens"])
        self._ins.selected_cached_tokens_total.inc(read["cached_tokens"])
        self._ins.selecting_decode_rows_total.inc(read["selecting_rows"])
        return read

    def _routing_read(self, counts) -> dict:
        """Attributes for the decode span of a model with routed experts:
        what the step's program counted over its routed layers, fetched
        behind the tokens (``HybridDecoderLM.decode_step_paged``): the
        live rows' assignments that fell on held experts, the held
        experts some row chose, the fullest expert's rows a layer
        (summed), and layers x experts held; also summed into the
        instruments."""
        read = dict(zip(("assignments_held", "experts_touched",
                         "expert_load_max", "expert_slots"),
                        (int(c) for c in counts)))
        for name, count in read.items():
            getattr(self._ins, f"routed_{name}_total").inc(count)
        return read

    def _decode_all_spec(self, active: List[int]) -> None:
        """Speculative decode over every occupied slot: one draft
        propose scan + one ragged target verify + one draft sync step
        — three fixed-shape dispatches for up to ``gamma + 1`` tokens
        per row. Acceptance is per ROW (a row whose draft guessed well
        advances further than its neighbors — no min-over-batch
        conservatism), and eos or the per-request token budget can
        truncate an extension mid-burst. Compiled shapes depend only
        on ``(max_slots, gamma)``."""
        g = self._spec.gamma
        tok = np.zeros((self.max_slots,), np.int32)
        pos = np.zeros((self.max_slots,), np.int32)
        for sid in active:
            st = self._slots[sid]
            tok[sid] = st.last_token
            pos[sid] = st.pos
        was_warm = ("spec:propose" in self._warm
                    and "spec:verify" in self._warm)
        if self.temperature > 0.0:
            r_draft, r_acc = self._next_key(), self._next_key()
        else:
            r_draft = r_acc = self._zero_key
        if self._chaos is not None:
            self._chaos.on_dispatch()
        with trace.span("serving/decode_dispatch",
                        rows=len(active)) as disp:
            tok_d, pos_d = self._h2d(tok), self._h2d(pos)
            props, qlogits, self._d_kv_pool = self._propose_jit(
                self._d_params, self._d_bufs, tok_d, pos_d,
                self._d_kv_pool, self._slot_tables(draft=True),
                r_draft, self._temp())
            emit, n_acc, self._kv_pool = self._spec_verify_jit(
                self._params, self._buffers, tok_d, props,
                qlogits, pos_d, self._kv_pool, self._slot_tables(),
                r_acc, self._temp())
            with trace.span("serving/fetch_tokens"):
                emit_np = np.asarray(emit)    # blocks on both dispatches
                n_np = np.asarray(n_acc)
        self._warm.update(("spec:propose", "spec:verify"))
        now = time.monotonic()
        # the span's warm-only wall to ledger, cost model, and loop
        # busy — one measurement, three reconciling views
        wall = disp.duration
        self._loop_obs.dispatch("decode_dispatch", wall, warm=was_warm)
        self._cost.charge("decode", wall, warm=was_warm)
        # draft sync BEFORE the next round can propose: a
        # FULL-acceptance row is missing exactly one draft KV write
        # (the propose scan never fed its gamma-th proposal through
        # the draft), so rewrite each row's last accepted token at its
        # own position — partial-acceptance rows rewrite identical
        # values in place, so one fixed-shape ragged dispatch serves
        # all rows. Skipped entirely when NO row fully accepted (their
        # scans already wrote everything); the program is warmed at
        # construction, so the conditional launch can never read as a
        # post-warmup compile. Enqueued async; the data dependency on
        # the draft pool orders it against the next propose.
        if any(int(n_np[sid]) == g for sid in active):
            sync_tok = np.zeros((self.max_slots,), np.int32)
            sync_pos = np.zeros((self.max_slots,), np.int32)
            for sid in active:
                n_r = int(n_np[sid])
                sync_tok[sid] = (tok[sid] if n_r == 0
                                 else int(emit_np[sid, n_r - 1]))
                sync_pos[sid] = pos[sid] + n_r
            self._d_kv_pool = self._d_sync_jit(
                self._d_params, self._d_bufs, self._h2d(sync_tok),
                self._h2d(sync_pos), self._d_kv_pool,
                self._slot_tables(draft=True))
        # burst lengths FIRST (pure), so the dispatch wall is
        # attributed before any handle can finalize — a late charge
        # against an already-finalized record would leak out of the
        # tenant aggregates and break conservation
        bursts = {}
        proposed = accepted = 0
        for sid in active:
            st = self._slots[sid]
            n_r = int(n_np[sid])
            proposed += g
            accepted += n_r
            st.handle.spec_proposed += g
            st.handle.spec_accepted += n_r
            room = st.handle.max_new_tokens - st.delivered
            toks = emit_np[sid, :min(n_r + 1, room)]
            if self.eos_id is not None:
                hits = np.flatnonzero(toks == self.eos_id)
                if hits.size:     # eos mid-extension: stop AT it
                    toks = toks[:hits[0] + 1]
            bursts[sid] = toks
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        self._ins.spec_proposed_tokens_total.inc(proposed)
        self._ins.spec_accepted_tokens_total.inc(accepted)
        if proposed:
            self._ins.spec_acceptance_ratio.observe(accepted / proposed)
        if was_warm:
            # the round's wall splits by each row's DELIVERED tokens:
            # billing follows useful work, not slot occupancy — and
            # the weights still sum to 1, so tenant device-second
            # sums conserve the measured busy tally (tested)
            total = sum(len(b) for b in bursts.values()) or 1
            self._usage.charge_dispatch(
                "decode", wall,
                [(getattr(self._slots[sid].handle, "_usage", None),
                  len(b) / total) for sid, b in bursts.items()],
                rows_advanced=len(active), capacity_rows=self.max_slots)
        for sid in active:
            self._deliver_burst(sid, bursts[sid], now)

    def _deliver_burst(self, sid: int, toks, now: float) -> None:
        """Stream one decode round's extension (1..gamma+1 tokens, in
        order) into the slot's handle, advancing the slot position by
        exactly the delivered count — the variable-advance invariant:
        afterwards the slot's KV covers ``[0, pos)`` and the last
        delivered token's KV is not yet cached, same as a 1-token
        step. Observes the inter-token histogram per TOKEN (the burst
        gap split evenly across its tokens, so histogram count keeps
        equalling delivered tokens), records ONE ``decode_token``
        event per burst carrying ``accepted=``, and finishes the row
        at eos / token budget."""
        st = self._slots[sid]
        h = st.handle
        m = len(toks)
        gap = (now - st.last_token_at) / m
        last = int(toks[-1])
        for t in toks:
            st.delivered += 1
            h._deliver(int(t), now)
            self._ins.inter_token_seconds.observe(gap)
        st.pos += m
        st.last_token = last
        st.last_token_at = now
        rec = getattr(h, "_usage", None)
        if rec is not None:
            self._usage.delivered(rec, m)
        self._ins.decode_tokens_total.inc(m)
        self._rec.record("request/decode_token", h.request_id,
                         service=self.service_name, slot=sid,
                         token=last, n=st.delivered, accepted=m)
        if (self.eos_id is not None and last == self.eos_id) \
                or st.delivered >= h.max_new_tokens:
            self._release(sid, None, "finished")

    # ------------------------------------------------------- plumbing
    def _temp(self):
        return self._temp_const

    def _next_key(self):
        if self.temperature <= 0.0:
            return self._zero_key  # greedy: the key is never consumed
        self._key, sub = jax.random.split(self._key)
        return self._h2d(sub)

    def _release(self, sid: int, error: Optional[Exception],
                 reason: str) -> None:
        st = self._slots[sid]
        # donate BEFORE the slot is surrendered: the slot's KV covers
        # positions [0, st.pos) — the prompt plus every delivered token
        # except the last (whose KV the next decode step would have
        # written), so the donated key is exactly prompt +
        # generated[:-1]. Cancelled/timed-out slots donate too: their
        # KV satisfies the same invariant, and a timed-out long prompt
        # is exactly the request most likely to be RETRIED — the retry
        # then pays O(novel-suffix), not a second full prefill.
        tokens = np.concatenate(
            [st.handle.prompt,
             np.asarray(st.handle._tokens[:-1], np.int32)])
        self._maybe_donate(sid, tokens, st.handle.request_id, st.snaps,
                           st.prompt_entry)
        self._release_snaps(st.snaps)
        self._free_slot_table(sid)
        self._slots[sid] = None
        self._ins.evicted_total.inc()
        if reason == "finished":
            self._ins.finished_total.inc()
        else:
            self._count_drop(reason)
        self._finish_handle(st.handle, error, reason)

    def _finish_dropped(self, h: RequestHandle, err: Exception) -> None:
        kind = ("cancelled" if isinstance(err, RequestCancelled)
                else "timed_out")
        self._count_drop(kind)
        self._finish_handle(h, err, kind)

    def _count_drop(self, kind: str) -> None:
        (self._ins.cancelled_total if kind == "cancelled"
         else self._ins.timed_out_total).inc()
