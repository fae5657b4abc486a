"""Batched serving: continuous batching, block-table paged KV (shared
device page pool), on-device sampling, self-drafting speculative
decoding, async dispatch/commit decode streams over the spike-coded
wire, and an SLO harness (trace-driven workloads, fault injection).

``EngineConfig`` knobs (the ones that shape the serving regime):

===============  ========================================================
``async_depth``  Decode steps the host may dispatch ahead of the oldest
                 un-synced step.  0 (default): classic synchronous loop.
                 1: step t+1 launches before step t's tokens are fetched
                 — host scheduling overlaps device compute; greedy
                 streams are token-identical to 0 (fuzz-enforced).
                 With ``spec_k > 0`` drafting joins the pipeline, so
                 only admission prefill overlaps the in-flight verify.
``spec_k``       Draft tokens per speculative verify step (0: vanilla
                 decode).  One batched forward scores all spec_k+1
                 positions per slot through the same coded boundaries;
                 greedy acceptance is token-identical to ``spec_k=0``.
                 Recurrent-state families force 0 (no rollback).
``drafter``      Who proposes those spec_k tokens.  ``"ngram"``
                 (default): host-side prompt-lookup over each slot's
                 committed history (``NGramDrafter``) — free, but the
                 host must see step t's tokens before it can draft step
                 t+1, so ``async_depth`` can only overlap admission
                 prefill.  ``"heads"``: learned draft heads
                 (``models.draft_heads``; train via
                 ``examples/train_hnn_lm.py --draft-heads``) riding the
                 verify step itself — acceptance, correction and the
                 next step's drafts are all computed on device, the
                 verify feed chains device-to-device, and verify
                 dispatches pipeline under ``async_depth > 0`` with NO
                 host join between them.  Needs a ``"draft_heads"``
                 subtree in params (typed ``EngineConfigError``
                 otherwise) with at least ``spec_k`` heads.  Both
                 drafters are greedy-token-identical to ``spec_k=0``
                 (fuzz-enforced across drafter x spec_k x async_depth x
                 codec x disagg).
``num_pages``    KV page-pool size, independent of ``num_slots *
                 max_seq``.  0: dense-equivalent default (can never
                 exhaust before the slots do); smaller is the paging
                 payoff — slots share the pool, exhaustion is the typed
                 ``PagePoolExhausted``.
``page_size``    Positions per KV page.  Admission maps only
                 ``ceil(prompt_len / page_size)`` pages; decode maps one
                 more page per ``page_size`` generated tokens
                 (alloc-on-extend).
``attn_kernel``  Decode/verify attention path.  ``"fused"`` (default):
                 the Pallas kernel walks the allocator's compacted
                 per-shard page lists — page gather, online-softmax
                 flash decode and the int8 wire epilogue in ONE kernel,
                 no ``[B, pages*page_size, Hkv, dh]`` gather in HBM, per
                 shard cost ``ceil(len / (page_size * tp))`` pages
                 instead of the full block-table width.  ``"reference"``:
                 the dense gather + ``verify_attention_partial`` path —
                 the oracle the kernel is fuzz-checked against
                 (token-identical greedy streams, enforced in
                 tests/test_paged_decode.py).  Anything else is a typed
                 ``EngineConfigError``.
``preempt``      Graceful degradation under pool pressure (default on):
                 a mid-flight ``PagePoolExhausted`` drains the pipeline
                 (limbo pages rejoin the pool) and then evicts +
                 re-queues the YOUNGEST slot of the starving group,
                 restarting it from scratch on re-admit — greedy streams
                 stay bit-identical to an uninterrupted run
                 (fuzz-enforced), so only latency pays.  False: the
                 typed error propagates to the caller's own policy.
``disagg``       Disaggregated prefill/decode (default off; needs a
                 dp >= 2 mesh).  The first ``prefill_groups`` dp groups
                 own admission prefill; the rest own decode.  Each
                 admitted request's paged KV (and any recurrent-state
                 rows) migrates to its decode group in ONE ppermute onto
                 pages the decode group mapped at matching per-shard
                 positions; admission pre-checks BOTH sides (slot, pages,
                 mirrored placement) so a started prefill can never
                 strand.  Greedy streams are token-identical to the
                 colocated engine (fuzz-enforced across spec_k x
                 async_depth x codec x kv_wire).
``prefill_groups``  How many dp groups ``disagg`` reserves for prefill
                 (default 1; must leave >= 1 decode group).
``kv_wire``      Migration wire format: ``"fp"`` moves KV pages at pool
                 dtype; ``"coded"`` moves per-page pow2-absmax int8
                 (~0.3x the bytes at dh=16) whose power-of-two scales
                 make encode/decode exactly idempotent on the pool — so
                 the coded wire is also token-identical, not just close
                 (see ``repro.core.boundary.coded_kv_migrate``).
``router``       Decode-group choice per migration: ``"load"`` (default)
                 picks the group with the fewest pages in use + limbo
                 (ties to the lowest id), ``"rr"`` round-robins over
                 mirror-capable groups.
===============  ========================================================

SLO harness knobs (``repro.serving.workload`` / ``repro.serving.slo``):

==================  =====================================================
``RequestClass``    One tenant's traffic model: ``poisson`` or bursty
                    ``onoff`` arrivals at ``rate`` req/s, prompt/gen
                    length ranges, a long-context ``tail_p``/``tail_len``
                    minority, temperature.
``PRESETS``         Named trace mixes (``steady`` / ``bursty`` /
                    ``longtail`` / ``multitenant``) scaled to the engine
                    budget; ``replay`` drives an engine through a trace
                    on a deterministic logical clock (or wall clock).
``SLOTargets``      Per-request TTFT/TPOT targets the attainment numbers
                    in ``SLOMonitor.report()`` are judged against.
``FaultPlan``       Seeded per-tick fault probabilities (``p_preempt``,
                    ``p_replica_loss``, ``p_suspend``) the
                    ``FaultInjector`` rolls once per tick — same seed,
                    same faults, so identity tests replay exactly.
``wire_streams_     ``SLOMonitor`` pricing table: step kind -> per-
per_step``          collective {stream -> bytes} of one compiled step,
                    from ``engine.wire_stream_profile()`` (psum / head
                    all-gather / partial combine / kv-migrate, parsed
                    out of the step HLO).  Every tick then records a
                    ``wire_streams`` split summing to its scalar
                    ``wire_bytes``; unknown step kinds warn instead of
                    silently pricing at 0, and migration bytes pending
                    at drain flush into a terminal ``drain`` event.
==================  =====================================================
"""
from .draft import NGramDrafter
from .engine import (WARMUP_RID, EngineConfig, Request, ServingEngine,
                     make_engine_decode_step, make_engine_heads_verify_step,
                     make_engine_prefill_step, make_engine_verify_step)
from .errors import (CacheOverflowError, EngineConfigError,
                     PagePoolExhausted, SchedulerStall, SlotsExhausted)
from .kv_cache import PagedKVCache, SlotAllocator
from .sampling import SamplingConfig, sample, sample_verify
from .slo import FaultInjector, FaultPlan, SLOMonitor, SLOTargets
from .workload import (PRESETS, RequestClass, Trace, TracedRequest,
                       make_trace, preset_trace, replay, zoo_mix)

__all__ = ["CacheOverflowError", "EngineConfig", "EngineConfigError",
           "FaultInjector", "FaultPlan", "NGramDrafter", "PRESETS",
           "PagePoolExhausted", "PagedKVCache", "Request", "RequestClass",
           "SLOMonitor", "SLOTargets", "SamplingConfig", "SchedulerStall",
           "ServingEngine", "SlotAllocator", "SlotsExhausted", "Trace",
           "TracedRequest", "WARMUP_RID", "make_trace", "preset_trace",
           "replay", "sample", "sample_verify", "zoo_mix",
           "make_engine_decode_step", "make_engine_heads_verify_step",
           "make_engine_prefill_step", "make_engine_verify_step"]
