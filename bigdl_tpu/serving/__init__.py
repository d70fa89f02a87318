"""bigdl_tpu.serving — continuous-batching LM inference.

The serving-at-scale layer (BigDL 2.0's north-star capability, arxiv
2204.01715): a persistent device-resident decode loop over one paged
KV pool, replacing batch-at-a-time request/response dispatch with
token-granular continuous batching —

- ``ContinuousBatchingEngine`` (``engine``): the loop thread, the page
  pool every request holds its KV in, mid-flight chunked-prefill
  admission (batched ``prefill_rows`` wide through one ragged dispatch
  per round), and per-token slot eviction/reuse. Compiled shapes
  depend only on ``max_slots``/``prefill_rows``/``(max_pages,
  page_size)`` — never on load. Pass ``draft=`` (plus ``spec_gamma``) for SPECULATIVE decode:
  the draft proposes gamma tokens for every live slot in one scan,
  the target verifies them in one ragged dispatch, and each row
  accepts its own variable-length extension — greedy output stays
  token-identical, decode dispatches per token drop by the acceptance
  rate (``SpeculationPolicy``). Pass ``mesh=`` (a model-axis device
  mesh) for TENSOR-PARALLEL serving: params Megatron-shard, every KV
  pool shards its heads dimension, and each compiled program runs as
  one SPMD dispatch with jit-inserted collectives — token-identical
  to the unsharded engine, jit gauge still flat.
- ``PagePool`` / ``BlockTable`` / ``PagedPrefixIndex`` (``paging``):
  the refcounted page allocator, one request's ordered view of its
  pages, and the host-side radix-trie index over token-id prefixes
  mapping to retained pages — a new request whose prompt shares a
  cached prefix shares those pages and skips prefill for the shared
  head (O(novel-suffix) TTFT); finished slots share their pages back
  under an LRU/ref-count policy, reclaimed (or demoted to a host tier)
  when the pool runs short.
- ``AdmissionQueue`` / ``PrefillPolicy`` (``scheduler``): bounded
  admission with backpressure, deadline/cancellation sweeps,
  QoS-ordered pop — (priority class, deadline slack, prefix-affinity
  score) under a per-class bounded bypass window — plus the
  prefill-vs-decode token budget and the per-tenant ``TokenBucket``
  rate limiter. Under overload the engine PREEMPTS lower-class slots
  (KV donated to the prefix index, automatic token-identical resume),
  SHEDS lowest-class admissions on SLO burn (``RequestShed``), and
  throttles over-budget tenants (``RequestRateLimited``) — see
  ``stats()["qos"]`` and ``engine(chaos=ChaosInjector())`` for drills.
- ``RequestHandle`` (``streams``): per-request streaming token
  iterator + blocking ``result()``; greedy output is token-identical
  to a lone ``model.generate`` call (tested).
- ``run_poisson_comparison`` (``benchmark``): the Poisson-arrival
  engine-vs-``GenerationService`` comparison behind
  ``bench.py --serving``.

Quick start::

    from bigdl_tpu.serving import ContinuousBatchingEngine

    with ContinuousBatchingEngine(model, max_slots=8,
                                  eos_id=eos) as engine:
        h = engine.submit(prompt_ids, max_new_tokens=128)
        for tok in h.tokens():      # streams as the loop decodes
            ...
        row = h.result()            # prompt + generated

Telemetry lands in the observability registry under
``bigdl_serving_*{service=...}`` (TTFT and inter-token histograms,
slot-occupancy gauge, admitted/evicted/timed-out counters, loop spans),
and every lifecycle transition lands in the flight recorder under the
handle's ``request_id`` (``handle.timeline()`` breakdowns,
``engine.debug_requests()`` / ``/debug/*`` endpoints, Chrome trace
export, and a crash postmortem from ``engine.healthz()``'s failing
loop — see ``bigdl_tpu.observability``). Usage is BILLED per request
under ``submit(..., tenant=...)``: the engine's ``UsageLedger``
attributes queue wait, prefilled vs prefix-reused tokens, delivered
tokens, KV byte-seconds held, and pro-rata dispatch device-seconds to
each tenant (``handle.usage()``, ``stats()["usage"]``,
``GET /debug/usage``, ``bigdl_serving_tenant_*`` counters).
"""

from bigdl_tpu.serving.chaos import ChaosFault, ChaosInjector
from bigdl_tpu.serving.engine import ContinuousBatchingEngine
from bigdl_tpu.serving.paging import (
    SCRATCH_PAGE, BlockTable, PagedPrefixIndex, PagePool, PrefixEntry,
)
from bigdl_tpu.serving.scheduler import (
    AdmissionQueue, PrefillPolicy, SpeculationPolicy, TokenBucket,
    page_fit_score, pages_needed,
)
from bigdl_tpu.serving.streams import (
    PRIORITIES, EngineDraining, EngineStopped, QueueFull,
    RequestCancelled, RequestError, RequestHandle,
    RequestRateLimited, RequestShed, RequestTimedOut,
)
from bigdl_tpu.serving.benchmark import (
    poisson_workload, quantized_quality_report,
    repeated_text_workload, run_poisson_comparison, run_qos_storm, run_quantized_comparison,
    run_shared_prefix_comparison, run_speculative_comparison,
    run_tp_comparison, run_working_set_sweep, shared_prefix_workload,
)

__all__ = [
    "ContinuousBatchingEngine",
    "ChaosInjector", "ChaosFault",
    "PagePool", "BlockTable", "PagedPrefixIndex", "PrefixEntry",
    "SCRATCH_PAGE",
    "AdmissionQueue", "PrefillPolicy", "SpeculationPolicy",
    "TokenBucket", "pages_needed", "page_fit_score",
]

__all__ += [
    "RequestHandle", "RequestError", "RequestCancelled",
    "RequestTimedOut", "RequestShed", "RequestRateLimited",
    "QueueFull", "EngineStopped", "EngineDraining", "PRIORITIES",
    "poisson_workload", "run_poisson_comparison",
    "shared_prefix_workload", "run_shared_prefix_comparison",
    "repeated_text_workload", "run_speculative_comparison",
    "run_tp_comparison", "run_working_set_sweep",
    "quantized_quality_report", "run_quantized_comparison",
    "run_qos_storm",
]
