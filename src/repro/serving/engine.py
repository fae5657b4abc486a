"""Continuous-batching serving engine over the spike-coded decode path.

One ``ServingEngine`` owns a fixed pool of request slots (the decode
batch), a block-table ``PagedKVCache`` (shared KV page pool; slot-major
recurrent state), and up to four compiled programs:

  prefill : B=1, fixed-length right-padded prompt -> slot-shaped cache
            + the first sampled token (logits taken at the true last
            prompt position via ``last_pos``)
  insert  : splice the prefilled cache into a free slot (donated)
  decode  : ONE step for ALL slots at once — per-slot positions,
            per-slot temperatures, fused distributed sampling — with the
            cache donated so serving is allocation-free at steady state
  verify  : (``spec_k > 0``) the speculative sibling of decode — scores
            K1 = spec_k+1 positions per slot in one batched forward
            (last committed token + spec_k draft tokens from the
            deterministic prompt-lookup drafter), writes KV for all of
            them, and returns K1 sampled tokens per slot.  The scheduler
            keeps the longest draft prefix matching the verify output
            plus the first correction token, then rolls the rejected
            tail's cache occupancy back (``PagedKVCache.rollback``).
            Greedy spec decoding is token-identical to ``spec_k=0``
            (asserted by tests/dist_scenarios.py ``serving_spec_parity``);
            the k-fold decode-boundary traffic of the verify step rides
            the same coded collectives, which is exactly the workload
            the spike wire makes cheap.  Families with recurrent state
            fall back to ``spec_k=0`` — their state cannot roll back.

Scheduling is classic continuous batching: every ``step()`` first admits
queued requests into free slots (prefill-then-decode interleaving), then
runs a single batched decode step; finished requests (max tokens, EOS,
or context full) retire immediately and their slot AND its KV pages
return to the free pool for the next admit.

Async decode streams (``EngineConfig.async_depth``): the engine is a
dispatch/commit pipeline.  ``dispatch()`` admits what fits and LAUNCHES
one batched device step without waiting for its tokens; ``commit()``
joins the oldest in-flight step (the only host sync on the hot path)
and applies its bookkeeping.  ``async_depth=0`` (default) commits every
dispatch immediately — the classic synchronous loop.  ``async_depth=1``
dispatches step t+1 before fetching step t's tokens: the token feed for
t+1 is step t's sampled-token DEVICE array chained straight back in
(XLA pipelines the two steps; the host never round-trips the values),
positions advance deterministically by one, and each dispatch stages
fresh double-buffered token/pos/block-table device arrays so host-side
scheduling for t+1 never races step t's transfers.  Retirement the host
can predict (token count, context end) is applied at dispatch so dead
slots stop being scheduled instantly; EOS is only discoverable at
commit, one step late under overlap — the already-dispatched zombie
step's token for that slot is discarded (slot identity, not index, ties
outputs to requests) and the pages it touched return through the
cache's deferred-free epoch, never to a concurrently-dispatched
snapshot.  Prefill admits are issued eagerly between decode dispatches
(the prefill overlaps the in-flight step; the new slot joins the batch
at the next dispatch), and admission itself never syncs: the prefill's
sampled first token stays a DEVICE array (``_Slot.pending_first``)
that the next decode feed patches straight in; its value folds into
host bookkeeping at the slot's first commit — by which point the sync
is free — or at a verify dispatch (drafting needs host tokens).

Graceful degradation: when a live slot cannot map its next page
(``PagePoolExhausted``) and ``EngineConfig.preempt`` is on, the engine
first drains the pipeline (deferred-free limbo pages rejoin the pool at
commit) and then evicts + re-queues the YOUNGEST slot of the starving
pool group, restarting it from scratch on re-admit — under greedy
sampling the restarted stream is bit-identical to an uninterrupted run,
so preemption shows up only in latency, never in tokens
(tests/test_faults.py).  ``preempt_slot`` exposes the same move to
fault injectors (``repro.serving.slo.FaultInjector``), and
``suspend``/``resume`` drain + snapshot + re-admit the whole engine for
simulated host preemption or replica loss.  Observer objects appended
to ``engine.observers`` receive ``on_submit`` / ``on_admit`` /
``on_first_token`` / ``on_finish`` / ``on_preempt`` / ``on_suspend``
lifecycle callbacks (see ``repro.serving.slo.SLOMonitor``).  Under greedy sampling the async schedule is
token-identical to the sync loop — per-slot streams are batch-
independent and the chained device tokens are the very same values the
host would have fed back — asserted by ``tests/test_engine_fuzz.py``
and the ``serving_parity``/``serving_spec_parity`` scenarios.  With
``spec_k > 0`` and the default ``drafter="ngram"`` the host must see
step t's accepted tokens before it can draft step t+1, so a verify
dispatch first joins the pipeline; what still overlaps is admission
prefill against the in-flight verify step.  ``drafter="heads"`` removes
that join: trained draft heads (``models.draft_heads``) ride the verify
step itself, so each step emits — on device — both its sampled tokens
AND the next step's complete feed (accepted token + head-argmax drafts)
plus chained positions, and the host dispatches verify t+1 against
those device arrays without ever syncing step t.  ``spec_k > 0`` then
composes with ``async_depth > 0`` exactly like the plain decode path
(acceptance bookkeeping is recomputed at commit from the synced feed
snapshot; truncation always retires the slot, so any column whose
device-side position ran ahead of the host is a zombie discarded by
slot identity, and page reclaim defers to the last in-flight commit of
the chain).  Heads drafting needs a trained ``"draft_heads"`` subtree
in the params tree (``examples/train_hnn_lm.py --draft-heads``);
non-heads programs strip it so their compiled signatures stay
trunk-only.

Admission maps only
``ceil(prompt_len / page_size)`` pages; each decode/verify step first
``ensure``s pages covering the positions it will write (alloc-on-
extend), raising typed ``PagePoolExhausted`` when the pool — not the
slot count — is the binding limit.  ``EngineConfig.num_pages`` sizes
the pool independently of ``num_slots * max_seq``; the default
reproduces the old dense reservation, so shrinking it is how the same
HBM holds more concurrent slots.

Every decode-path activation collective carries the spike/int8 wire:
D-space boundaries through ``repro.core.boundary.coded_psum`` /
``wire_roundtrip``, and the head-space exchanges — q/kv head gathers
(``coded_head_all_gather``) and the flash-decode partial combine
(``coded_combine_partials``, fed by the fused kernel's int8 epilogue) —
through per-token absmax int8.  The only uncoded decode-step traffic
left is the O(heads) LSE scalars riding the combine.

All per-slot computation is batch-independent — no reduction mixes
slots, int8 scales are per-token — so under greedy decoding a slot's
token stream is bit-identical whether it shares the batch with 0 or
``num_slots-1`` neighbours (asserted by tests/dist_scenarios.py
``serving_parity``).  Stochastic sampling is per-slot independent in
distribution, but draws its Gumbel noise from the slot row and the
engine's step counter, so sampled streams are reproducible only for a
fixed schedule, not across different batch compositions.

Correctness note on padded prefill: right-padding is exact for
attention-family models (pad KV beyond ``last_pos`` is masked by the
per-slot position and overwritten as decode advances).  Families with
recurrent state (ssm/rnn/hybrid) fold pad tokens into the prefill-final
state, so their prompts must arrive at exactly ``prefill_len`` tokens;
the engine enforces this.

Tracing: the scheduler's layers are host spans on the profiler's clock
(``jax.profiler.TraceAnnotation``, a no-op unless a profiler session is
active), named ``engine.*``: ``engine.step`` (``tick``) holds
``engine.dispatch`` — ``engine.admit`` (``rid``, ``prompt_len``; under it
``engine.prefill`` with its host input ``bytes`` and ``engine.insert``
with its ``pages``),
``engine.ensure_pages``, ``engine.stage`` (``bytes``) and
``engine.launch`` — and ``engine.commit`` — ``engine.commit.wait`` (the
host blocked on the device) and ``engine.commit.apply`` (bookkeeping;
``engine.retire`` with the ``rid`` of each request that finishes).
Counters beside ``tokens_generated``: ``staged_bytes`` (host bytes
handed to the device: every staged feed and each prefill's host inputs),
``kv_blocks_walked`` (page blocks one paged-decode kernel call of each
launched step computes, summed over slots and pool shards; each launch's
share is the ``kv_blocks`` stat of its ``engine.launch`` span),
``kv_pages_fetched`` (the pages of K, and as many of V, that such a call
copies: the mapped ones; each launch's share is its ``kv_pages`` stat),
``kv_pages_inserted`` (the pool pages the admission inserts wrote,
summed over shards: ``ceil(prompt_len / page_size)`` an admission; each
admission's share is the ``pages`` stat of its ``engine.insert`` span)
and ``queue_wait_s`` (summed submit -> admit host time).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ShapeCell
from ..launch.serve import strip_dp_specs
from ..launch.specs import (cache_specs, default_num_pages, make_context,
                            make_plan, prefill_handoff_specs,
                            serve_decode_input_specs,
                            serve_feed_specs, serve_heads_feed_specs,
                            serve_verify_input_specs, verify_shape_cell)
from ..launch.train import shard_params_specs
from ..models import common as MC
from ..models import draft_heads as DH
from ..models import model as M
from ..models import params as PR
from . import sampling
from .draft import NGramDrafter
from .errors import (CacheOverflowError, EngineConfigError,
                     PagePoolExhausted, SchedulerStall, SlotsExhausted)
from .kv_cache import PagedKVCache, pool_rows
from .sampling import SamplingConfig

__all__ = ["CacheOverflowError", "EngineConfig", "EngineConfigError",
           "PagePoolExhausted", "Request", "SchedulerStall",
           "ServingEngine", "SlotsExhausted", "WARMUP_RID",
           "make_engine_decode_step", "make_engine_heads_verify_step",
           "make_engine_prefill_step", "make_engine_verify_step"]


#: Reserved request id for ``warmup``'s throwaway request.  A fresh
#: ``object()`` compares equal only to itself, so no user-supplied rid
#: (int, str, uuid, ...) can ever collide with it in a results dict.
WARMUP_RID = object()


@dataclasses.dataclass
class Request:
    """One generation request."""

    rid: int
    prompt: Sequence[int]
    max_new_tokens: int = 16
    temperature: float = 0.0


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_slots: int = 4
    max_seq: int = 128
    prefill_len: int = 0           # 0 -> max_seq
    page_size: int = 64
    num_pages: int = 0             # KV pool size (0 -> dense-equivalent:
    #                                every slot can map pages_per_slot)
    top_k: int = 0
    top_p: float = 0.0
    eos_id: Optional[int] = None
    replicate_weights: bool = False
    seed: int = 0
    spec_k: int = 0                # draft tokens per verify step (0: off)
    drafter: str = "ngram"         # speculative draft source: "ngram"
    #                                (deterministic host-side prompt
    #                                lookup — needs committed tokens, so
    #                                every verify dispatch joins the
    #                                pipeline first) or "heads" (trained
    #                                draft heads evaluated ON DEVICE
    #                                inside the verify step — the feed
    #                                for step t+1 chains from step t
    #                                without a host sync, so spec_k
    #                                composes with async_depth; requires
    #                                a "draft_heads" params subtree)
    async_depth: int = 0           # decode steps the host may dispatch
    #                                ahead of the oldest un-synced step
    #                                (0: classic synchronous loop)
    preempt: bool = True           # on PagePoolExhausted mid-flight,
    #                                evict + re-queue the youngest slot
    #                                in the starving pool group instead
    #                                of failing the step (False: the
    #                                typed error propagates)


@dataclasses.dataclass
class _Slot:
    req: Request
    out: list
    drafter: Optional[NGramDrafter] = None
    #: uncommitted dispatched steps this slot participates in
    inflight: int = 0
    #: scheduled for future dispatches; False once the host knows (or
    #: can predict) the request is finished
    live: bool = True
    #: admission order (monotonic engine counter) — preemption picks
    #: victims youngest-first so the oldest request always progresses
    seq: int = 0
    #: the admit prefill's sampled first token, still a DEVICE [1] array
    #: (deferred first-token sync: the host never blocks on it at admit;
    #: the value folds into host bookkeeping at the slot's first commit,
    #: at verify dispatch, or when nothing else can run)
    pending_first: Optional[object] = None


@dataclasses.dataclass
class _Resume:
    """Queue entry for a suspended mid-generation request: re-admit with
    the committed tokens as part of the prompt (work-preserving) instead
    of restarting from scratch.

    The effective prefill prompt is ``req.prompt + prior``; the admitted
    slot's ``out`` is pre-seeded with ``prior`` so retirement limits,
    committed-position accounting and the final output all see the full
    request — under greedy sampling the re-prefilled continuation is
    token-identical to the uninterrupted run, so only latency, not
    output, records the suspension.  ``suspend`` only creates one when
    the combined length still fits the prefill path (and, for
    recurrent families, lands on a valid exact-length bucket);
    otherwise it falls back to the old restart-from-scratch entry.
    """

    req: Request
    prior: list                      # committed tokens at suspend time

    @property
    def rid(self):
        return self.req.rid


@dataclasses.dataclass
class _InFlight:
    """One dispatched, not-yet-committed batched device step."""

    kind: str                          # "decode" | "verify" | "verify_heads"
    #: (slot index, _Slot) pairs live at dispatch time — the OBJECT, not
    #: the index, ties the step's outputs to requests, so a slot retired
    #: (or even re-admitted) between dispatch and commit simply drops
    #: its column instead of corrupting the new occupant
    entries: list
    out: object                        # device token future [n] or [n,K1]
    drafts: Optional[np.ndarray] = None   # [n, spec_k] (ngram verify only)
    #: heads verify only: the DEVICE feed/pos snapshot this step scored
    #: — synced at commit to recompute acceptance host-side (the drafts
    #: never visit the host before the step that scores them runs)
    feed_in: Optional[object] = None      # device [n, K1]
    pos_in: Optional[object] = None       # device [n]


def make_engine_prefill_step(cfg, plan, mesh, scfg: SamplingConfig,
                             replicate_weights=False):
    """prefill(params, tokens[1,S], last_pos[1], temp[1], key) ->
    (first_token [1], cache).

    The cache is handed over in the pool's row layout
    (``prefill_handoff_specs``): each attention KV leaf lane-flat
    ``[U, 1, S, Hkv*dh]``, so the insert copies pages as they are."""
    _, pspecs, _ = shard_params_specs(cfg, plan)
    ctx = make_context(plan, "prefill")
    if replicate_weights:
        pspecs = strip_dp_specs(pspecs)
        ctx = ctx.with_(dp_size=1)
    _, cspecs = prefill_handoff_specs(plan)

    def step(params, tokens, last_pos, temp, key):
        logits, caches = M.forward_prefill(params, {"tokens": tokens}, ctx,
                                           last_pos=last_pos)
        tok = sampling.sample(logits, key, temp, tp=ctx.tp,
                              tp_size=ctx.tp_size, cfg=scfg)
        return tok, pool_rows(caches)

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, P(None, plan.tp), P(None), P(None), P()),
        out_specs=(P(None), cspecs), check_vma=False)
    return jax.jit(fn)


def make_engine_decode_step(cfg, plan, mesh, scfg: SamplingConfig,
                            page_size, num_pages,
                            replicate_weights=False):
    """decode(params, cache, token[B], pos[B], bt[B,PPS], clp[B,S,ppc],
    clo[B,S,ppc], temp[B], key) -> (next_token [B], cache) — cache
    donated.

    ``cache`` is the shared KV page pool (+ slot-major state leaves);
    ``bt`` the per-slot block table the attention writes K/V through;
    ``clp``/``clo`` the compacted per-shard page lists (local page rows
    / start positions) the paged-decode kernel walks.
    """
    _, pspecs, _ = shard_params_specs(cfg, plan)
    ctx = make_context(plan, "decode")
    if replicate_weights:
        pspecs = strip_dp_specs(pspecs)
        ctx = ctx.with_(dp_size=1)
    _, ispecs = serve_decode_input_specs(plan, page_size, num_pages)

    def step(params, cache, token, pos, bt, clp, clo, temp, key):
        aux = {"block_table": bt, "page_list": (clp, clo)}
        logits, cache = M.forward_decode(params, cache, token, pos, ctx,
                                         aux_extra=aux)
        tok = sampling.sample(logits, key, temp, tp=ctx.tp,
                              tp_size=ctx.tp_size, cfg=scfg)
        return tok, cache

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, ispecs["cache"], ispecs["token"], ispecs["pos"],
                  ispecs["bt"], ispecs["clp"], ispecs["clo"],
                  ispecs["temp"], ispecs["key"]),
        out_specs=(ispecs["token"], ispecs["cache"]), check_vma=False)
    return jax.jit(fn, donate_argnums=(1,))


def make_engine_verify_step(cfg, plan, mesh, scfg: SamplingConfig, spec_k,
                            page_size, num_pages,
                            replicate_weights=False):
    """verify(params, cache, tokens[B,K1], pos[B], bt[B,PPS], clp, clo,
    temp[B], key) -> (tokens_out [B,K1], cache) — cache donated.

    One batched forward over all K1 = spec_k+1 speculative positions of
    every slot; column j of ``tokens_out`` is the model's (greedy or
    sampled) next token after committing ``tokens[:, :j+1]``.  Reads and
    writes the same page pool + block table as the decode step, and
    takes the same compacted page lists for the paged-decode kernel
    (which covers K1 >= 1 with one code path).
    """
    _, pspecs, _ = shard_params_specs(cfg, plan)
    ctx = make_context(plan, "decode")
    if replicate_weights:
        pspecs = strip_dp_specs(pspecs)
        ctx = ctx.with_(dp_size=1)
    _, ispecs = serve_verify_input_specs(plan, spec_k, page_size, num_pages)

    def step(params, cache, tokens, pos, bt, clp, clo, temp, key):
        aux = {"block_table": bt, "page_list": (clp, clo)}
        logits, cache = M.forward_verify(params, cache, tokens, pos, ctx,
                                         aux_extra=aux)
        tok = sampling.sample_verify(logits, key, temp, tp=ctx.tp,
                                     tp_size=ctx.tp_size, cfg=scfg)
        return tok, cache

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, ispecs["cache"], ispecs["token"], ispecs["pos"],
                  ispecs["bt"], ispecs["clp"], ispecs["clo"],
                  ispecs["temp"], ispecs["key"]),
        out_specs=(ispecs["token"], ispecs["cache"]), check_vma=False)
    return jax.jit(fn, donate_argnums=(1,))


def make_engine_heads_verify_step(cfg, plan, mesh, scfg: SamplingConfig,
                                  spec_k, page_size, num_pages, max_seq,
                                  replicate_weights=False):
    """verify_heads(params, cache, tokens[B,K1], pos[B], bt, clp, clo,
    temp[B], key) -> (tokens_out [B,K1], feed_next [B,K1],
    pos_next [B], cache) — cache donated.

    The device-drafting sibling of ``make_engine_verify_step``: the same
    batched K1-position forward and sampler, but ``params`` carries a
    ``"draft_heads"`` subtree (replicated — see ``models.draft_heads``)
    and the step ALSO computes, entirely on device, everything the next
    verify dispatch needs:

      acc       longest prefix of the fed drafts ``tokens[:, 1:]``
                matching the sampled outputs ``tok[:, :-1]`` — the exact
                acceptance rule the host applies at commit
      corr      the correction/bonus token ``tok[:, acc]`` (the last
                token the commit will keep)
      feed_next ``[corr, head-argmax drafts]``: the draft heads read the
                post-roundtrip hidden at the accepted position (h is
                replicated across tp ranks there, so replicated heads
                draft identically per rank with zero new collectives),
                project through the tp-sharded LM head, and take the
                distributed argmax
      pos_next  ``min(pos + acc + 1, max_seq)`` — the committed position
                the host will reach for any slot it neither truncates
                nor retires (truncation always retires, making the
                slot's later in-flight columns zombies)

    Chaining (feed_next, pos_next) into the next dispatch is what
    deletes the ngram drafter's host join: greedy identity still holds
    structurally because garbage drafts merely fail acceptance.
    """
    _, pspecs, _ = shard_params_specs(cfg, plan)
    hspecs = PR.specs_tree(DH.draft_head_defs(cfg, 1), plan.dp, plan.tp)
    ctx = make_context(plan, "decode")
    if replicate_weights:
        pspecs = strip_dp_specs(pspecs)
        hspecs = strip_dp_specs(hspecs)
        ctx = ctx.with_(dp_size=1)
    pspecs = dict(pspecs)
    pspecs["draft_heads"] = hspecs
    _, ispecs = serve_verify_input_specs(plan, spec_k, page_size, num_pages)
    k = spec_k

    def step(params, cache, tokens, pos, bt, clp, clo, temp, key):
        aux = {"block_table": bt, "page_list": (clp, clo)}
        logits, cache, h = M.forward_verify(params, cache, tokens, pos,
                                            ctx, aux_extra=aux,
                                            return_hidden=True)
        tok = sampling.sample_verify(logits, key, temp, tp=ctx.tp,
                                     tp_size=ctx.tp_size, cfg=scfg)
        match = (tokens[:, 1:] == tok[:, :-1]).astype(jnp.int32)
        acc = jnp.cumprod(match, axis=1).sum(axis=1)           # [B] 0..k
        corr = jnp.take_along_axis(tok, acc[:, None], axis=1)[:, 0]
        h_acc = jnp.take_along_axis(h, acc[:, None, None], axis=1)[:, 0]
        z = DH.head_hiddens(params["draft_heads"], h_acc)      # [B,H,D]
        head = M._head_w(params, ctx)                          # [D,V_loc]
        dlog = (z @ head.astype(z.dtype)).astype(jnp.float32)
        if cfg.final_softcap:
            dlog = MC.softcap(dlog, cfg.final_softcap)
        drafts = sampling.dist_argmax(dlog, ctx.tp, ctx.tp_size)  # [B,H]
        feed = jnp.concatenate([corr[:, None], drafts[:, :k]], axis=1)
        pos_next = jnp.minimum(pos + acc + 1, max_seq)
        return tok, feed, pos_next, cache

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(pspecs, ispecs["cache"], ispecs["token"], ispecs["pos"],
                  ispecs["bt"], ispecs["clp"], ispecs["clo"],
                  ispecs["temp"], ispecs["key"]),
        out_specs=(ispecs["token"], ispecs["token"], ispecs["pos"],
                   ispecs["cache"]), check_vma=False)
    return jax.jit(fn, donate_argnums=(1,))


_RECURRENT_CACHE_KEYS = ("ssm_state", "rnn_state", "rwkv_state")


class ServingEngine:
    """Batched continuous-batching decode over a slot pool."""

    def __init__(self, cfg, mesh, params, ecfg: EngineConfig):
        if cfg.is_encdec:
            raise EngineConfigError("encoder-decoder serving: follow-on")
        self.cfg, self.mesh, self.params, self.ecfg = cfg, mesh, params, ecfg
        prefill_len = ecfg.prefill_len or ecfg.max_seq
        cell_dec = ShapeCell("serve_decode", ecfg.max_seq, ecfg.num_slots,
                             "decode")
        self.plan = make_plan(cfg, cell_dec, mesh)
        if not self.plan.batch_sharded:
            raise EngineConfigError(
                f"num_slots={ecfg.num_slots} must divide over the data axes "
                f"(dp_size={self.plan.dp_size})")
        if ecfg.max_seq % self.plan.tp_size != 0:
            raise EngineConfigError(
                f"max_seq={ecfg.max_seq} must be divisible by "
                f"tp_size={self.plan.tp_size}")
        if prefill_len % self.plan.tp_size != 0:
            raise EngineConfigError(
                f"prefill_len={prefill_len} must be divisible by "
                f"tp_size={self.plan.tp_size}")
        if ecfg.spec_k < 0:
            raise EngineConfigError(f"spec_k={ecfg.spec_k} must be >= 0")
        if ecfg.async_depth < 0:
            raise EngineConfigError(
                f"async_depth={ecfg.async_depth} must be >= 0")
        if ecfg.page_size < 1:
            raise EngineConfigError(f"page_size={ecfg.page_size} must be "
                                    ">= 1")
        shards = self.plan.dp_size * self.plan.tp_size
        self.num_pages = (ecfg.num_pages
                          or default_num_pages(self.plan, ecfg.page_size))
        if self.num_pages % shards != 0:
            raise EngineConfigError(
                f"num_pages={self.num_pages} must divide over the "
                f"dp x tp devices ({shards}) so the page pool shards "
                "evenly")
        if ecfg.drafter not in ("ngram", "heads"):
            raise EngineConfigError(
                f"drafter={ecfg.drafter!r}: expected 'ngram' or 'heads'")
        cell_pre = ShapeCell("serve_admit", prefill_len, 1, "prefill")
        self.plan_pre = make_plan(cfg, cell_pre, mesh)
        self.prefill_len = prefill_len
        self._has_state = any(
            k in _RECURRENT_CACHE_KEYS
            for pos in cache_specs(self.plan)[0].values() for k in pos)
        # recurrent state folds every token in and cannot roll back a
        # rejected draft: those families serve vanilla (spec_k=0)
        self.spec_k = 0 if self._has_state else ecfg.spec_k
        self.drafter_kind = ecfg.drafter
        if ecfg.drafter == "heads":
            if ecfg.spec_k <= 0:
                raise EngineConfigError(
                    "drafter='heads' requires spec_k > 0 (the heads only "
                    "ever draft inside speculative verify steps)")
            if self.spec_k > 0:
                if not (isinstance(params, dict)
                        and "draft_heads" in params):
                    raise EngineConfigError(
                        "drafter='heads' needs trained draft-head params: "
                        "the params tree has no 'draft_heads' subtree — "
                        "train one (examples/train_hnn_lm.py "
                        "--draft-heads K) and restore its checkpoint")
                n_heads = int(params["draft_heads"]["w1"].shape[0])
                if n_heads < self.spec_k:
                    raise EngineConfigError(
                        f"drafter='heads': {n_heads} draft heads < "
                        f"spec_k={self.spec_k} (one head per draft "
                        "position)")
        #: the params tree WITHOUT the draft-heads subtree: every program
        #: except the heads verify step compiles against trunk-only
        #: shard_map in_specs, so an extra params key would be a pytree
        #: mismatch — strip it once here
        self._trunk = params
        if isinstance(params, dict) and "draft_heads" in params:
            self._trunk = {kk: v for kk, v in params.items()
                           if kk != "draft_heads"}

        scfg = SamplingConfig(top_k=ecfg.top_k, top_p=ecfg.top_p)
        self._scfg = scfg
        self._prefill = make_engine_prefill_step(
            cfg, self.plan_pre, mesh, scfg, ecfg.replicate_weights)
        #: exact-length prefill buckets for recurrent families: seq len
        #: -> (compiled prefill step, its plan) — lazy, the default
        #: full-length bucket is pre-registered
        self._prefill_buckets = {prefill_len: (self._prefill,
                                               self.plan_pre)}
        self._decode = make_engine_decode_step(
            cfg, self.plan, mesh, scfg, ecfg.page_size, self.num_pages,
            ecfg.replicate_weights)
        self._verify = None
        if self.spec_k > 0:
            self.plan_ver = make_plan(
                cfg, verify_shape_cell(ecfg.max_seq, ecfg.num_slots,
                                       self.spec_k), mesh)
            if self.drafter_kind == "heads":
                self._verify = make_engine_heads_verify_step(
                    cfg, self.plan_ver, mesh, scfg, self.spec_k,
                    ecfg.page_size, self.num_pages, ecfg.max_seq,
                    ecfg.replicate_weights)
            else:
                self._verify = make_engine_verify_step(
                    cfg, self.plan_ver, mesh, scfg, self.spec_k,
                    ecfg.page_size, self.num_pages,
                    ecfg.replicate_weights)
        self.cache = PagedKVCache(self.plan, self.plan_pre, mesh,
                                  ecfg.page_size, self.num_pages)

        n = ecfg.num_slots
        self._tokens = np.zeros(n, np.int32)
        self._pos = np.zeros(n, np.int32)
        self._temp = np.zeros(n, np.float32)
        self._slots: list[Optional[_Slot]] = [None] * n
        self._queue: deque[Request] = deque()
        self._retired: list = []       # finished (request, tokens) pairs
        #                                awaiting pickup by step()
        # -- dispatch/commit pipeline state --
        self.async_depth = ecfg.async_depth
        self._inflight: deque[_InFlight] = deque()
        if self.spec_k > 0 and self.drafter_kind == "heads":
            self._feed_specs = serve_heads_feed_specs(
                self.plan, ecfg.page_size, self.spec_k)
        else:
            self._feed_specs = serve_feed_specs(self.plan, ecfg.page_size,
                                                self.spec_k)
        #: last decode dispatch's sampled-token DEVICE array: the token
        #: feed of the next dispatch chains it back in without a host
        #: round-trip (None until the first decode dispatch)
        self._tok_dev = None
        #: slots whose next feed token must come from the host shadow
        #: (``self._tokens``) — slots whose deferred first token has
        #: been folded to the host since the last decode dispatch
        self._tok_dirty: set[int] = set()
        #: slot -> device [1] first-token array from the admit prefill:
        #: the next decode feed patches these straight from the device
        #: (the value never visits the host on the admission path)
        self._tok_pending: dict[int, object] = {}
        #: heads drafter: the last verify dispatch's chained
        #: (feed [B,K1], pos [B]) DEVICE arrays — the next dispatch's
        #: inputs, with dirty/pending slots patched in (None until the
        #: first heads verify dispatch)
        self._vfeed_dev = None
        self._vpos_dev = None
        self._admit_seq = 0
        self._key = jax.random.PRNGKey(ecfg.seed)
        self._tick = 0
        self._ticks = 0                # step() calls: the engine.step tag
        self.tokens_generated = 0
        self.decode_steps = 0
        self.staged_bytes = 0      # host bytes handed to the device
        self.kv_blocks_walked = 0  # paged-decode kernel blocks computed
        self.kv_pages_fetched = 0  # paged-decode kernel pages copied
        self.kv_pages_inserted = 0  # pool pages the admit inserts wrote
        self.queue_wait_s = 0.0    # summed submit -> admit host time
        #: id(request) -> host time of its submit, until its first admit
        self._submitted: dict = {}
        self.spec_commits = 0      # tokens committed by verify steps
        self.spec_verifies = 0     # (slot, verify-step) participations
        self.pipelined_dispatches = 0  # verify dispatches launched while
        #                                another step was still un-synced
        #                                — the host join the heads drafter
        #                                deletes; structurally 0 for
        #                                drafter="ngram" (tests assert
        #                                both directions)
        self.preemptions = 0       # evict + re-queue events (pool
        #                            pressure or injected faults)
        self.suspends = 0          # drain + snapshot + resume events
        #: observability hooks: objects whose optional ``on_submit`` /
        #: ``on_admit`` / ``on_first_token`` / ``on_finish`` /
        #: ``on_preempt`` / ``on_suspend`` methods are
        #: called at the matching lifecycle points (see
        #: ``repro.serving.slo``); the per-tick ``on_step`` hook stays
        #: on ``run(on_step=...)``
        self.observers: list = []

    # -- request lifecycle -------------------------------------------------

    def submit(self, req: Request):
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (admit always "
                             "samples one token from the prefill logits)")
        P_len = len(req.prompt)
        if not 0 < P_len <= self.prefill_len:
            raise ValueError(
                f"prompt len {P_len} not in (0, {self.prefill_len}]")
        if self._has_state and P_len % self.plan.tp_size != 0:
            # right-padding would corrupt the prefill-final recurrent
            # state, so these families prefill through an EXACT-length
            # bucket instead — any multiple of tp_size (the sequence
            # sharding granularity) up to prefill_len is admissible
            raise ValueError(
                "recurrent-state families prefill exact-length buckets: "
                f"prompt len {P_len} must be a multiple of tp_size "
                f"({self.plan.tp_size})")
        alloc = self.cache.allocator
        if alloc.pages_needed(P_len) > alloc.pages_per_group:
            raise ValueError(
                f"prompt needs {alloc.pages_needed(P_len)} KV pages but a "
                f"pool group only holds {alloc.pages_per_group} "
                f"(num_pages={self.num_pages}): the request could never "
                "be admitted")
        self._queue.append(req)
        self._submitted[id(req)] = time.perf_counter()
        self._emit("on_submit", req.rid, P_len)

    def _emit(self, event: str, *args):
        for obs in self.observers:
            fn = getattr(obs, event, None)
            if fn is not None:
                fn(*args)

    def _next_key(self):
        self._tick += 1
        return jax.random.fold_in(self._key, self._tick)

    @staticmethod
    def _entry_parts(entry):
        """(request, prior committed tokens, effective prefill prompt)
        for a queue entry — ``Request`` or a suspend-time ``_Resume``."""
        if isinstance(entry, _Resume):
            return (entry.req, entry.prior,
                    list(entry.req.prompt) + list(entry.prior))
        return entry, [], list(entry.prompt)

    def _prefill_for(self, P_len: int):
        """(padded seq len, compiled prefill step, its plan) for a
        ``P_len``-token prompt.

        Attention families right-pad into the single full-length prefill
        (exact — padded positions are causally masked and never
        attended).  Recurrent families fold every position into the
        running state, so padding is NOT exact: they prefill through an
        exact-length bucket instead, compiled lazily per distinct prompt
        length (``submit`` guarantees tp_size-divisibility).
        """
        if not self._has_state:
            return self.prefill_len, self._prefill, self.plan_pre
        if P_len not in self._prefill_buckets:
            cell = ShapeCell("serve_admit", P_len, 1, "prefill")
            plan_b = make_plan(self.cfg, cell, self.mesh)
            prog = make_engine_prefill_step(
                self.cfg, plan_b, self.mesh, self._scfg,
                self.ecfg.replicate_weights)
            self._prefill_buckets[P_len] = (prog, plan_b)
        prog, plan_b = self._prefill_buckets[P_len]
        return P_len, prog, plan_b

    def _admit(self, entry):
        """Prefill a queue entry (``Request`` or ``_Resume``) into a free
        slot — with NO host sync.

        The prefill/insert launches are asynchronous, so under
        ``async_depth > 0`` they overlap whatever decode/verify step is
        currently in flight (XLA orders them behind it on the donated
        cache buffers).  The first sampled token stays a DEVICE array
        (``_Slot.pending_first``): the next decode dispatch patches it
        straight into the chained token feed, so admission never blocks
        the host on a fresh prefill.  The value folds into host
        bookkeeping (``out``, EOS check, drafter seed) at the slot's
        first commit — by which time the prefill has long executed and
        the sync is free — or earlier when the spec path needs host
        tokens to draft.
        """
        req, prior, prompt = self._entry_parts(entry)
        P_len = len(prompt)
        with TraceAnnotation("engine.admit", rid=req.rid, prompt_len=P_len):
            t_sub = self._submitted.pop(id(req), None)
            if t_sub is not None:
                self.queue_wait_s += time.perf_counter() - t_sub
            S_pre, prefill_fn, plan_pre = self._prefill_for(P_len)
            toks = np.zeros((1, S_pre), np.int32)
            toks[0, :P_len] = np.asarray(prompt, np.int32)
            last = np.array([P_len - 1], np.int32)
            temp = np.array([req.temperature], np.float32)
            nbytes = toks.nbytes + last.nbytes + temp.nbytes
            self.staged_bytes += nbytes
            with TraceAnnotation("engine.prefill", bytes=nbytes):
                first, pre_cache = prefill_fn(self._trunk, toks, last, temp,
                                              self._next_key())
            # admit maps ceil(P_len/page_size) pages — O(prompt), not
            # O(max_seq); each decode step maps the next page on demand
            with TraceAnnotation("engine.insert") as span:
                slot, pages = self.cache.admit(pre_cache, P_len,
                                               plan_pre=plan_pre)
                span.set_metadata(pages=pages)
            self.kv_pages_inserted += pages
            st = _Slot(req, list(prior), None, seq=self._admit_seq,
                       pending_first=first)
            self._admit_seq += 1
            self._slots[slot] = st
            self._pos[slot] = P_len
            self._temp[slot] = req.temperature
            self._tok_dirty.discard(slot)
            self._tok_pending[slot] = first
            self.tokens_generated += 1
            self._emit("on_admit", req.rid, slot)
            # retirement the host can predict WITHOUT the token value (count
            # and context limits) applies now so the slot is never scheduled;
            # the deferred value still folds later for the output/EOS
            if (self._n_committed(st) >= st.req.max_new_tokens
                    or self._committed_pos(st) >= self.ecfg.max_seq):
                st.live = False

    def _n_committed(self, st: _Slot) -> int:
        """Tokens the request has generated as far as the host is
        concerned: the committed ``out`` plus the admit prefill's
        deferred first token (generated, value just not yet synced)."""
        return len(st.out) + (1 if st.pending_first is not None else 0)

    def _committed_pos(self, st: _Slot) -> int:
        """The slot's committed cache occupancy / next write position.

        Derived, not stored: admit leaves ``prompt + [first]`` at
        occupancy ``len(prompt)``, and every committed token advances
        both the token count and the position by one — so the
        dispatch-side ``self._pos`` (which runs ahead of the host under
        overlap) can never be confused with what has been committed.
        """
        return len(st.req.prompt) + self._n_committed(st) - 1

    def _fold_first(self, slot: int, st: _Slot) -> bool:
        """Sync the deferred admit token into host bookkeeping.

        Returns True iff the slot is still occupied by ``st`` afterwards
        (folding runs the EOS/limit retirement check the admit path
        deferred, so it may retire the slot).  No-op when nothing is
        pending.  The sync is effectively free at every call site: the
        prefill that produced the value has already been overlapped by
        at least one dispatched step (or the pipeline is idle).
        """
        if st.pending_first is None:
            return self._slots[slot] is st
        first = int(np.asarray(st.pending_first)[0])
        st.pending_first = None
        st.out.append(first)
        self._tokens[slot] = first
        if self._tok_pending.pop(slot, None) is not None:
            # the device-side feed patch never consumed this value; the
            # next feed takes it from the (now correct) host shadow
            self._tok_dirty.add(slot)
        if (self.spec_k > 0 and self.drafter_kind == "ngram"
                and st.drafter is None):
            # st.out holds the committed stream so far — prior tokens
            # carried across a work-preserving suspend plus this first
            # token — so the drafter sees the same history an
            # uninterrupted run would have fed it incrementally (the
            # heads drafter keeps no host state: drafts live on device)
            st.drafter = NGramDrafter(list(st.req.prompt) + st.out)
        self._emit("on_first_token", st.req.rid)
        self._maybe_retire(slot, first)
        return self._slots[slot] is st

    def _fold_pending(self):
        """Fold every slot still carrying a deferred first token."""
        for i, st in enumerate(self._slots):
            if st is not None and st.pending_first is not None:
                self._fold_first(i, st)

    def _maybe_retire(self, slot: int, tok: int):
        st = self._slots[slot]
        done = (len(st.out) >= st.req.max_new_tokens
                or (self.ecfg.eos_id is not None and tok == self.ecfg.eos_id)
                or self._committed_pos(st) >= self.ecfg.max_seq)
        if done:
            # evict zeroes the slot's block-table row (-1), so the stale
            # pos/token the retired row still carries into the next
            # batched step can only produce dropped writes — a recycled
            # page can never be corrupted by its previous owner.  Under
            # overlap the freed pages park in the cache's deferred-free
            # limbo until every dispatched snapshot has committed.
            with TraceAnnotation("engine.retire", rid=st.req.rid):
                st.live = False
                self.cache.evict(slot)
                self._slots[slot] = None
                self._retired.append((st.req, st.out))
                self._emit("on_finish", st.req.rid, len(st.out))

    # -- scheduling --------------------------------------------------------

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def queue_depth(self) -> int:
        """Requests admitted-but-waiting (the backpressure signal SLO
        monitors and admission routers read every tick)."""
        return len(self._queue)

    @property
    def idle(self) -> bool:
        """Nothing queued, live or in flight, and no finished request left
        for ``step()`` to hand out (a flush outside ``step()``, such as a
        fault's preempt or suspend, can retire one after the last tick)."""
        return (not self._queue and self.num_active == 0
                and not self._inflight and not self._retired)

    def _live_slots(self) -> list:
        return [i for i, s in enumerate(self._slots)
                if s is not None and s.live]

    def active_slots(self) -> list:
        """Occupied slot indices, oldest admission first — the fault
        injector's victim menu (``[-1]`` is the youngest)."""
        return sorted((i for i, s in enumerate(self._slots)
                       if s is not None),
                      key=lambda i: self._slots[i].seq)

    def _can_admit_next(self) -> bool:
        """Admission gate for the queue head — limbo-aware.

        ``can_admit`` counts limbo pages as UNAVAILABLE.  The old gate
        checked the free list alone, so an admit could claim the last
        fresh pages while limbo still owed pages to the pipeline — the
        very next ``ensure`` then starved mid-flight: a typed
        ``PagePoolExhausted`` with ``preempt=False``, needless
        preemption churn / pipeline-drain bubbles with the default
        rescue path.  Deferring instead is cheap and live: every tick
        commits at least down to ``async_depth``, so limbo pages rejoin
        their free deques within ``async_depth`` ticks and the queue
        head admits as soon as the pool genuinely has room (an
        ``after_flush`` counterfactual is available on
        ``SlotAllocator.can_admit`` for schedulers that would rather
        trade the overlap bubble for earlier admission).
        """
        _, _, prompt = self._entry_parts(self._queue[0])
        return self.cache.allocator.can_admit(len(prompt))

    # -- faults / graceful degradation -------------------------------------

    def preempt_slot(self, slot: int, kind: str = "preempt"):
        """Evict ``slot`` and re-queue its request at the FRONT of the
        admission queue, restarting generation from scratch on re-admit.

        Restart-from-scratch keeps the house token-identity rule: under
        greedy sampling the regenerated stream is bit-identical to the
        uninterrupted run (per-slot streams are batch-independent and
        greedy ignores the PRNG key), so a preemption is invisible in
        the final output — only in the request's latency.  Tokens
        generated so far are discarded rather than resumed: resuming
        mid-stream would need the slot's KV snapshot off-device, which
        is exactly the cost preemption exists to avoid.  Pages freed
        here park in the allocator's deferred-free limbo while any
        dispatched step's snapshot still names them, and an in-flight
        step's column for this slot is discarded at commit by
        slot-object identity — safe to call mid-pipeline (the fault
        injector does).  ``on_preempt`` observers fire with
        ``(rid, kind)``; ``kind`` distinguishes ``pool_pressure`` from
        injected faults (``injected_preempt``, ``replica_loss``).
        """
        st = self._slots[slot]
        if st is None:
            raise ValueError(f"preempt_slot: slot {slot} is free")
        st.live = False
        self.cache.evict(slot)
        self._slots[slot] = None
        self._tok_pending.pop(slot, None)
        self._tok_dirty.discard(slot)
        self.preemptions += 1
        self._queue.appendleft(st.req)
        self._emit("on_preempt", st.req.rid, kind)

    def _suspend_entry(self, st: _Slot):
        """Queue entry preserving ``st``'s committed work where the
        prefill path can re-ingest it: a ``_Resume`` carrying the
        committed tokens when ``prompt + committed`` still fits the
        prefill window (and, for recurrent families, lands on a valid
        exact-length bucket and a group can hold its pages) — otherwise
        the old restart-from-scratch ``Request``.  Greedy identity holds
        either way; only the work redone differs."""
        committed = list(st.out)
        if committed:
            L = len(st.req.prompt) + len(committed)
            alloc = self.cache.allocator
            if (L <= self.prefill_len
                    and alloc.pages_needed(L) <= alloc.pages_per_group
                    and (not self._has_state
                         or L % self.plan.tp_size == 0)):
                return _Resume(st.req, committed)
        return st.req

    def suspend(self) -> list:
        """Simulated host preemption: drain the pipeline, snapshot every
        pending request, and release all slots + pages.

        Returns the entries still owed output — mid-generation slots in
        admission order, then the untouched queue — for ``resume``.
        Mid-generation requests are snapshotted WORK-PRESERVING: the
        tokens committed so far ride along as a ``_Resume`` entry and
        re-admission prefills ``prompt + committed`` instead of
        regenerating it token by token (falling back to
        restart-from-scratch only when the combined length no longer
        fits the prefill path — see ``_suspend_entry``).  Greedy token
        identity to the uninterrupted run holds in both modes; requests
        that FINISHED during the drain retire normally and are not
        suspended.  After this the engine holds no device-side request
        state: pages are back in the pool and the chained token feed is
        reset, so the caller may checkpoint, migrate, or simply
        ``resume`` in place.
        """
        self.flush()
        self._fold_pending()
        reqs = []
        for i in self.active_slots():
            st = self._slots[i]
            self.cache.evict(i)
            self._slots[i] = None
            reqs.append(self._suspend_entry(st))
        self._emit("on_suspend", [r.rid for r in reqs])
        self._tok_pending.clear()
        self._tok_dirty.clear()
        self._tok_dev = None
        self._vfeed_dev = None
        self._vpos_dev = None
        reqs.extend(self._queue)
        self._queue.clear()
        self.suspends += 1
        return reqs

    def resume(self, requests: Sequence[Request]):
        """Re-admit ``suspend``'s snapshot at the front of the queue in
        its original order; admission proceeds on the next tick."""
        for r in reversed(list(requests)):
            self._queue.appendleft(r)

    def step(self) -> list:
        """One scheduler tick: dispatch what can run, commit what must.

        Returns the requests finished this tick as (request, tokens)
        pairs.  With ``async_depth=0`` every dispatch commits
        immediately — the classic synchronous loop.  With
        ``async_depth=d > 0`` the host keeps up to ``d`` device steps in
        flight: a tick dispatches step t+1 and only then joins step
        t+1-d, so host scheduling (admission, retirement, page
        bookkeeping) runs while the device computes.  When nothing can
        be dispatched (no live slot) the pipeline drains fully so the
        engine always reaches ``idle``.

        Admission is gated on BOTH a free slot and free pool pages for
        the prompt (``can_admit``); a request that doesn't fit stays
        queued.  Before a device step launches, every scheduled slot
        maps pages covering the positions the step will write
        (alloc-on-extend) — if a live slot cannot grow because its pool
        group is empty, the engine degrades gracefully
        (``EngineConfig.preempt``, default on): drain the pipeline so
        limbo pages rejoin the pool, then evict + re-queue the YOUNGEST
        slot of the starving group and retry (``_ensure_for_step``).
        With ``preempt=False`` — or when the group holds a single live
        slot, which preemption could never help — ``PagePoolExhausted``
        propagates: the pool, not the slot count, is the binding limit,
        and the operator sized ``num_pages`` below even one request's
        demand.
        """
        self._ticks += 1
        with TraceAnnotation("engine.step", tick=self._ticks):
            dispatched = self.dispatch()
            target = self.async_depth if dispatched else 0
            while len(self._inflight) > target:
                self.commit()
            return self._drain_retired()

    def dispatch(self) -> bool:
        """Admit what fits, then LAUNCH one batched decode (or k-token
        verify) step without waiting for its tokens.  Returns True iff a
        device step was dispatched (its results surface at a later
        ``commit()``)."""
        with TraceAnnotation("engine.dispatch"):
            return self._dispatch()

    def _dispatch(self) -> bool:
        while self._queue and self._can_admit_next():
            self._admit(self._queue.popleft())
        if self.spec_k > 0 and self.drafter_kind == "heads":
            # device-side drafting: the previous verify step already
            # emitted the next feed (accepted token + head drafts) and
            # chained positions — NO pipeline join.  Only slots retired
            # by prediction at admit (never scheduled, so no commit will
            # ever fold them) need their deferred token folded here,
            # exactly like the plain decode path below.
            for i, st in enumerate(self._slots):
                if (st is not None and not st.live
                        and st.pending_first is not None):
                    self._fold_first(i, st)
            live = self._live_slots()
            if not live:
                return False
            self._dispatch_verify_heads(live)
            return True
        if self.spec_k > 0:
            # drafting reads committed tokens: join the pipeline first
            # (the admissions above already overlapped the in-flight
            # verify step — that is the spec path's share of the win),
            # then fold every deferred admit token so the drafters and
            # the host token shadow the verify feed reads are real
            self.flush()
            self._fold_pending()
            live = self._live_slots()
            if not live:
                return False
            self._dispatch_verify(live)
            return True
        # slots retired-by-prediction at admit (max_new_tokens == 1,
        # context already full) are never scheduled, so no commit will
        # ever fold their deferred token: fold it here or they leak
        for i, st in enumerate(self._slots):
            if st is not None and not st.live and st.pending_first is not None:
                self._fold_first(i, st)
        live = self._live_slots()
        if not live:
            return False
        self._dispatch_decode(live)
        return True

    def commit(self):
        """Join the OLDEST in-flight step — the single host sync of the
        decode hot path — and apply its bookkeeping: append/accept
        tokens, retire finished requests, roll back rejected drafts,
        release deferred page frees."""
        if not self._inflight:
            raise ValueError("commit: no dispatched step in flight")
        with TraceAnnotation("engine.commit"):
            rec = self._inflight.popleft()
            with TraceAnnotation("engine.commit.wait"):
                out = np.asarray(rec.out)   # host sync: the step has fully
                #                             executed once this returns
            with TraceAnnotation("engine.commit.apply"):
                self.cache.note_commit()
                self.decode_steps += 1
                if rec.kind == "verify_heads":
                    self._commit_verify_heads(rec, out)
                elif rec.kind == "verify":
                    self._commit_verify(rec, out)
                else:
                    self._commit_decode(rec, out)

    def flush(self):
        """Commit every in-flight dispatched step (drain the pipeline)."""
        while self._inflight:
            self.commit()

    def _drain_retired(self) -> list:
        """Hand the retirements accumulated so far to the caller.

        Retired (request, tokens) pairs buffer on the engine, not in a
        ``step()``-local, so a typed mid-step failure (e.g.
        ``PagePoolExhausted`` from an ``ensure``) cannot discard results
        of requests that already finished earlier in the same step —
        they surface from the next successful ``step()``.
        """
        out, self._retired = self._retired, []
        return out

    # -- dispatch side -----------------------------------------------------

    def _stage(self, arr, spec):
        """Fresh device copy of a host feed array with the step's own
        input sharding (the double buffer: the in-flight step keeps the
        previous copy, the host is free to mutate ``arr`` for the next
        tick).  The host copy is made here, synchronously: a transfer
        may still read its source after ``device_put`` returns, and on
        the CPU backend an array can even alias host memory.  Its bytes
        count in ``staged_bytes``."""
        arr = np.array(arr)
        self.staged_bytes += arr.nbytes
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def _launch_span(self):
        """The ``engine.launch`` span of a step, its ``kv_blocks`` and
        ``kv_pages`` stats the page blocks one of the step's paged-decode
        kernel calls computes and the pages it copies at the pages mapped
        now, counted into ``kv_blocks_walked`` and ``kv_pages_fetched``."""
        blocks = self.cache.kv_blocks_walked()
        pages = self.cache.kv_pages_fetched()
        self.kv_blocks_walked += blocks
        self.kv_pages_fetched += pages
        return TraceAnnotation("engine.launch", kv_blocks=blocks,
                               kv_pages=pages)

    def _stage_step_feeds(self):
        """Staged (block table, page-list rows, page-list positions,
        temperatures): the feeds every step kind takes from the host."""
        f = self._feed_specs
        return (self._stage(self.cache.block_table, f["bt"]),
                self._stage(self.cache.page_list_loc, f["clp"]),
                self._stage(self.cache.page_list_pos, f["clo"]),
                self._stage(self._temp, f["temp"]))

    def _token_feed(self):
        """Device token feed for the next decode dispatch.

        Chains the previous dispatch's sampled-token device array
        straight back in — the values never visit the host — and
        patches freshly admitted slots straight from their prefill's
        DEVICE first-token array (``_tok_pending``), so admission never
        syncs either: the whole prefill -> first decode chain stays on
        device.  Slots whose deferred token was folded to the host in
        the meantime re-enter from the host shadow (``_tok_dirty``).
        Slots retired between the two dispatches keep whatever the
        device array carries: their block-table rows are already -1 (or
        owned by a new occupant that is itself patched here), so the
        garbage can only produce dropped writes and discarded outputs.
        """
        if self._tok_dev is None:
            self._tok_dirty.clear()
            feed = self._stage(self._tokens, self._feed_specs["token"])
        else:
            feed = self._tok_dev
            if self._tok_dirty:
                idx = np.asarray(sorted(self._tok_dirty), np.int32)
                feed = feed.at[idx].set(self._tokens[idx])
                self._tok_dirty.clear()
        if self._tok_pending:
            for s in sorted(self._tok_pending):
                feed = feed.at[s].set(self._tok_pending[s][0])
            self._tok_pending.clear()
        return feed

    def _ensure_for_step(self, live, need):
        """Map every page the next step will write (``need(slot)`` is the
        occupancy it must cover) — with graceful degradation.

        On ``PagePoolExhausted`` (the pool, not the slot count, is the
        binding limit) and ``ecfg.preempt``: first drain the pipeline —
        deferred-free limbo pages from late retirements/rollbacks rejoin
        the pool at commit — and if the starving slot's group is STILL
        dry, evict + re-queue the YOUNGEST slot in that group and retry.
        Youngest-first preserves the progress guarantee: the oldest
        request is never the victim, so every preemption strictly
        advances the admission order and the scheduler cannot livelock.
        A group with a single live slot is never preempted against
        itself — the typed error propagates, exactly as with
        ``preempt=False`` (the operator sized ``num_pages`` below even
        one request's demand).  Returns the (possibly shrunk) live list.
        ``ensure`` is idempotent per page, so retrying the loop after a
        partial pass never double-maps.
        """
        alloc = self.cache.allocator
        while True:
            try:
                for i in live:
                    self.cache.ensure(i, need(i))
                return live
            except PagePoolExhausted:
                if not self.ecfg.preempt:
                    raise
                starving = i
            if self._inflight:
                self.flush()      # commits release limbo pages; they may
                #                   also retire slots (late EOS) or fold
                #                   deferred tokens — refresh and retry
                live = [j for j in live
                        if self._slots[j] is not None and self._slots[j].live]
                continue
            grp = alloc.group_of(starving)
            victims = [j for j in live if alloc.group_of(j) == grp]
            if len(victims) < 2:
                # preempting the sole live slot of its group would free
                # its pages only to starve again on re-admit: retry once
                # so the typed error propagates (unless the flush above
                # retired the starving slot, in which case this passes)
                for i in live:
                    self.cache.ensure(i, need(i))
                return live
            victim = max(victims, key=lambda j: self._slots[j].seq)
            self.preempt_slot(victim, kind="pool_pressure")
            live = [j for j in live if j != victim]

    def _dispatch_decode(self, live):
        # the step writes KV at position pos: map its page first.  Under
        # overlap a slot here may already be finished at a
        # still-uncommitted step (late EOS) — its page comes back
        # through the deferred-free epoch at that step's commit.
        with TraceAnnotation("engine.ensure_pages"):
            live = self._ensure_for_step(live,
                                         lambda i: int(self._pos[i]) + 1)
        if not live:
            return
        b0 = self.staged_bytes
        with TraceAnnotation("engine.stage") as span:
            tok = self._token_feed()
            pos = self._stage(self._pos, self._feed_specs["pos"])
            bt, clp, clo, temp = self._stage_step_feeds()
            span.set_metadata(bytes=self.staged_bytes - b0)
        with self._launch_span():
            out, self.cache.buffers = self._decode(
                self._trunk, self.cache.buffers, tok, pos, bt, clp, clo,
                temp, self._next_key())
            self.cache.note_dispatch()
        self._tok_dev = out
        self._inflight.append(
            _InFlight("decode", [(i, self._slots[i]) for i in live], out))
        for i in live:
            st = self._slots[i]
            st.inflight += 1
            self._pos[i] += 1
            # predictable retirement (token count, context end) applies
            # at dispatch so a finished slot never gets scheduled again;
            # EOS is only discoverable at commit, one step late under
            # overlap, and that zombie step's column is discarded.
            # _n_committed counts the deferred admit token too.
            if (self._n_committed(st) + st.inflight >= st.req.max_new_tokens
                    or int(self._pos[i]) >= self.ecfg.max_seq):
                st.live = False

    def _dispatch_verify(self, live):
        """Launch one speculative step: draft k per slot, score all k+1
        positions in one batched forward.  Acceptance happens at commit.

        Under greedy sampling the committed stream is token-identical to
        ``spec_k=0``: drafts only ever get accepted when they equal the
        argmax the vanilla step would have produced, and the first
        correction token is that argmax itself.
        """
        k = self.spec_k
        n = self.ecfg.num_slots
        # the verify step writes KV at pos..pos+k (clipped at the
        # context end): map those pages before launching; the rejected
        # tail's pages roll back once acceptance is known
        with TraceAnnotation("engine.ensure_pages"):
            live = self._ensure_for_step(
                live, lambda i: min(int(self._pos[i]) + k + 1,
                                    self.ecfg.max_seq))
        if not live:
            return
        drafts = np.zeros((n, k), np.int32)
        for i in live:
            drafts[i] = self._slots[i].drafter.propose(k)
        b0 = self.staged_bytes
        with TraceAnnotation("engine.stage") as span:
            tok_in = self._stage(
                np.concatenate([self._tokens[:, None], drafts], axis=1),
                self._feed_specs["vtoken"])
            # this feed just consumed the host token shadow for EVERY
            # slot: nothing stays dirty for a future feed
            self._tok_dirty.clear()
            pos = self._stage(self._pos, self._feed_specs["pos"])
            bt, clp, clo, temp = self._stage_step_feeds()
            span.set_metadata(bytes=self.staged_bytes - b0)
        with self._launch_span():
            out, self.cache.buffers = self._verify(
                self._trunk, self.cache.buffers, tok_in, pos, bt, clp, clo,
                temp, self._next_key())
            self.cache.note_dispatch()
        self._inflight.append(
            _InFlight("verify", [(i, self._slots[i]) for i in live], out,
                      drafts=drafts))
        for i in live:
            self._slots[i].inflight += 1

    def _verify_feed(self):
        """Device (feed [B,K1], pos [B]) for the next heads-drafter
        verify dispatch.

        Chains the previous verify step's device-emitted feed/positions
        straight back in — drafts and acceptance never visit the host
        between dispatches.  Slots that need re-seeding patch in exactly
        like ``_token_feed``: host-folded slots (``_tok_dirty``) from
        the host shadow at their committed position, freshly admitted
        slots (``_tok_pending``) from their prefill's DEVICE first-token
        array.  A re-seeded row is ``[tok]*K1`` — repeat-token drafts,
        garbage-safe under longest-prefix acceptance (worst case the
        step degrades to vanilla decode for that slot for one step).
        """
        K1 = self.spec_k + 1
        if self._vfeed_dev is None:
            self._tok_dirty.clear()
            feed = self._stage(np.repeat(self._tokens[:, None], K1, axis=1),
                               self._feed_specs["vtoken"])
            pos = self._stage(self._pos, self._feed_specs["vpos"])
        else:
            feed, pos = self._vfeed_dev, self._vpos_dev
            if self._tok_dirty:
                idx = np.asarray(sorted(self._tok_dirty), np.int32)
                feed = feed.at[idx].set(self._tokens[idx, None])
                pos = pos.at[idx].set(self._pos[idx])
                self._tok_dirty.clear()
        if self._tok_pending:
            for s in sorted(self._tok_pending):
                feed = feed.at[s].set(self._tok_pending[s][0])
                pos = pos.at[s].set(int(self._pos[s]))
            self._tok_pending.clear()
        return feed, pos

    def _dispatch_verify_heads(self, live):
        """Launch one speculative step with DEVICE-side drafting — no
        pipeline join, so under ``async_depth > 0`` verify t+1 overlaps
        verify t exactly like plain decode steps do.

        Page mapping covers the worst case of every un-synced chain
        link: each in-flight step (plus this one) can advance a slot by
        at most spec_k+1 positions past the last COMMITTED position, so
        ``ensure`` maps up to ``pos + (k+1) * (inflight+1)``.  The
        unreclaimed tail this over-mapping leaves is bounded by
        ``(k+1) * (async_depth+1)`` positions per slot and is trimmed
        page-exactly by the chain's last commit (``st.inflight == 0``).
        """
        k = self.spec_k
        with TraceAnnotation("engine.ensure_pages"):
            live = self._ensure_for_step(
                live, lambda i: min(
                    int(self._pos[i])
                    + (k + 1) * (self._slots[i].inflight + 1),
                    self.ecfg.max_seq))
        if not live:
            return
        if self._inflight:
            # a verify launched over a still-un-synced step: the host
            # join the ngram drafter forces is provably gone (tests
            # assert this counter stays 0 for drafter="ngram")
            self.pipelined_dispatches += 1
        b0 = self.staged_bytes
        with TraceAnnotation("engine.stage") as span:
            feed, pos = self._verify_feed()
            bt, clp, clo, temp = self._stage_step_feeds()
            span.set_metadata(bytes=self.staged_bytes - b0)
        with self._launch_span():
            out, feed_next, pos_next, self.cache.buffers = self._verify(
                self.params, self.cache.buffers, feed, pos, bt, clp, clo,
                temp, self._next_key())
            self.cache.note_dispatch()
        self._vfeed_dev, self._vpos_dev = feed_next, pos_next
        self._inflight.append(
            _InFlight("verify_heads",
                      [(i, self._slots[i]) for i in live], out,
                      feed_in=feed, pos_in=pos))
        for i in live:
            self._slots[i].inflight += 1

    # -- commit side -------------------------------------------------------

    def _commit_decode(self, rec: _InFlight, out: np.ndarray):
        for i, st in rec.entries:
            if self._slots[i] is not st:
                continue     # retired at an earlier commit (late EOS),
                #              preempted, or slot re-admitted: discard
                #              the zombie column
            st.inflight -= 1
            if not self._fold_first(i, st):
                continue     # the deferred admit token was EOS: the slot
                #              retired at fold and this step's column is
                #              a zombie (its write already landed beyond
                #              the retired occupancy — dropped on device)
            tok = int(out[i])
            st.out.append(tok)
            self._tokens[i] = tok
            self.tokens_generated += 1
            self._maybe_retire(i, tok)

    def _commit_verify(self, rec: _InFlight, out: np.ndarray):
        """Accept the longest draft prefix matching the verify output
        plus the model's correction token; roll the rejected tail's
        cache occupancy back page-exactly."""
        k = self.spec_k
        drafts = rec.drafts
        for i, st in rec.entries:
            if self._slots[i] is not st:
                continue
            st.inflight -= 1
            a = 0
            while a < k and drafts[i, a] == out[i, a]:
                a += 1
            committed = 0
            for j in range(a + 1):                 # accepted drafts + fixup
                tok = int(out[i, j])
                st.out.append(tok)
                st.drafter.extend([tok])
                self._tokens[i] = tok
                self._pos[i] += 1
                self.tokens_generated += 1
                committed += 1
                if (len(st.out) >= st.req.max_new_tokens
                        or (self.ecfg.eos_id is not None
                            and tok == self.ecfg.eos_id)
                        or self._pos[i] >= self.ecfg.max_seq):
                    break
            self.cache.rollback(i, int(self._pos[i]))
            self.spec_commits += committed
            self.spec_verifies += 1
            self._maybe_retire(i, int(self._tokens[i]))

    def _commit_verify_heads(self, rec: _InFlight, out: np.ndarray):
        """Commit one heads-drafter verify step.

        The drafts this step scored lived only on device (the previous
        step's chained feed), so acceptance is recomputed here from the
        synced feed snapshot (``rec.feed_in``) against the sampled
        outputs — the same longest-prefix rule the device applied when
        it chained the NEXT step's feed and positions.  For a slot the
        host neither truncates nor retires, the committed position lands
        exactly on the chained device position, keeping every later
        in-flight step of the chain valid; truncation (max_new_tokens,
        EOS, context end) always retires the slot, so its later columns
        are zombies discarded by slot-object identity — the same
        structural safety valve the ngram path leans on.

        Page reclaim is deferred while the slot still has in-flight
        steps (they may legitimately write past this step's occupancy);
        the chain's LAST commit trims page-exactly, and eviction frees
        everything regardless.
        """
        k = self.spec_k
        feed = np.asarray(rec.feed_in)
        base = np.asarray(rec.pos_in)
        for i, st in rec.entries:
            if self._slots[i] is not st:
                continue
            st.inflight -= 1
            if not self._fold_first(i, st):
                continue
            a = 0
            while a < k and feed[i, a + 1] == out[i, a]:
                a += 1
            committed = 0
            pos = int(base[i])
            for j in range(a + 1):             # accepted drafts + fixup
                tok = int(out[i, j])
                st.out.append(tok)
                self._tokens[i] = tok
                pos += 1
                committed += 1
                self.tokens_generated += 1
                if (len(st.out) >= st.req.max_new_tokens
                        or (self.ecfg.eos_id is not None
                            and tok == self.ecfg.eos_id)
                        or pos >= self.ecfg.max_seq):
                    break
            self._pos[i] = pos
            self.spec_commits += committed
            self.spec_verifies += 1
            if st.inflight == 0:
                self.cache.rollback(i, pos)
            self._maybe_retire(i, int(self._tokens[i]))

    @property
    def mean_accepted_len(self) -> float:
        """Mean tokens committed per (slot, verify-step) — >1.0 means the
        drafter is paying for itself."""
        return self.spec_commits / max(self.spec_verifies, 1)

    def run(self, requests: Sequence[Request], max_steps: int = 100000,
            on_step=None):
        """Serve ``requests`` to completion; {rid: generated tokens}.

        ``on_step`` (optional) is called as ``on_step(self)`` after
        every scheduler tick — benches timestamp per-step latency
        through it instead of re-implementing this drive loop (and
        losing its typed ``SchedulerStall`` diagnostics).
        """
        for r in requests:
            self.submit(r)
        results = {}
        for _ in range(max_steps):
            for req, out in self.step():
                results[req.rid] = out
            if on_step is not None:
                on_step(self)
            if self.idle:
                break
        if not self.idle:
            raise SchedulerStall(
                f"run: {self.num_active} slots still active, "
                f"{len(self._queue)} requests queued and "
                f"{len(self._inflight)} steps in flight after "
                f"{max_steps} steps")
        return results

    def warmup(self, prompt: Sequence[int]):
        """Compile the prefill/insert/decode/verify programs off the
        clock by serving one throwaway request, then zero the throughput
        stats.  The throwaway uses the reserved ``WARMUP_RID`` sentinel,
        which no user-supplied rid can equal."""
        self.run([Request(rid=WARMUP_RID, prompt=prompt, max_new_tokens=2)])
        self.reset_stats()

    def reset_stats(self):
        """Zero the throughput counters.

        Any in-flight dispatched step is committed FIRST: a pipelined
        step straddling the reset would otherwise surface its tokens
        (and its device time) inside the measured run — warmup would
        leak work into the numbers it exists to keep clean.  Results
        retired by the flush stay buffered for the next ``step()``.
        """
        self.flush()
        self.tokens_generated = 0
        self.decode_steps = 0
        self.staged_bytes = 0
        self.kv_blocks_walked = 0
        self.kv_pages_fetched = 0
        self.kv_pages_inserted = 0
        self.queue_wait_s = 0.0
        self.spec_commits = 0
        self.spec_verifies = 0
        self.pipelined_dispatches = 0
        self.preemptions = 0
        self.suspends = 0
        # the pool high-water mark is a stat too: warmup's throwaway
        # admission must not overstate the measured run's peak
        self.cache.peak_pages_in_use = self.cache.allocator.pages_in_use

    # -- introspection -----------------------------------------------------

    def _compile(self, program, ins, params=None):
        """lower+compile ``program`` on its input specs.  ``params``
        defaults to the trunk-only tree (what every program except the
        heads verify step compiles against)."""
        return program.lower(
            self._trunk if params is None else params,
            self.cache.buffers, ins["token"], ins["pos"],
            ins["bt"], ins["clp"], ins["clo"], ins["temp"],
            ins["key"]).compile()

    def _wire_stats(self, compiled, tokens_per_step: float):
        """Parse the ICI collectives of a compiled step; (CollectiveStats,
        total wire bytes per token across the mesh at ``tokens_per_step``
        tokens committed per step)."""
        from ..launch import roofline as RL
        stats = RL.parse_collectives(compiled.as_text())
        ndev = self.plan.dp_size * self.plan.tp_size
        per_tok = stats.wire_bytes * ndev / max(tokens_per_step, 1e-9)
        return stats, per_tok

    def compiled_decode_step(self):
        """The batched decode step, compiled on its input specs: the
        ``jax.stages.Compiled`` whose HLO text and memory analysis show
        what the device runs (collectives, whether the fused kernel is
        there as a ``tpu_custom_call``)."""
        ins, _ = serve_decode_input_specs(self.plan, self.ecfg.page_size,
                                          self.num_pages)
        return self._compile(self._decode, ins)

    def decode_wire_stats(self):
        """Parse the compiled batched decode step's collectives.

        Returns (CollectiveStats, wire_bytes_per_token): per-device ICI
        bytes of ONE decode step, scaled to total bytes per generated
        token across the mesh.
        """
        return self._wire_stats(self.compiled_decode_step(),
                                self.ecfg.num_slots)

    def verify_wire_stats(self, accepted_len: float = 1.0):
        """Parse the compiled k-token verify step's collectives.

        Returns (CollectiveStats, wire_bytes_per_token): per-device ICI
        bytes of ONE verify step, scaled to total bytes per *committed*
        token across the mesh at the given mean accepted length.  The
        verify step moves ~(spec_k+1)x the decode step's D-space
        activation bytes through the same coded boundaries — the traffic
        multiplier the spike wire absorbs; dividing by ``accepted_len``
        shows what the wire actually pays per token kept.
        """
        if self._verify is None:
            raise EngineConfigError("verify_wire_stats: spec_k == 0")
        ins, _ = serve_verify_input_specs(self.plan_ver, self.spec_k,
                                          self.ecfg.page_size,
                                          self.num_pages)
        compiled = self._compile(
            self._verify, ins,
            params=self.params if self.drafter_kind == "heads" else None)
        return self._wire_stats(compiled, self.ecfg.num_slots * accepted_len)

    def wire_stream_profile(self):
        """Per-collective wire streams of each compiled step kind.

        Returns ``{step kind -> {stream kind -> bytes}}`` where the
        bytes are one device step's TOTAL die-to-die traffic across the
        mesh, split by semantic stream (``psum`` / ``head_all_gather`` /
        ``partial_combine`` / ... — the ``CollectiveStats.by_stream``
        classification from ``launch.roofline.parse_collectives``).  The
        ``"decode"`` entry is always present; ``"verify"`` joins it when
        ``spec_k > 0``, so a monitor fed this profile prices BOTH step
        kinds the engine can emit (a recurrent-family fallback run only
        ever ticks ``"decode"``).  Feed it to
        ``SLOMonitor(wire_streams_per_step=...)``: the step trace then
        carries the per-collective breakdown the cycle-level NoC
        co-simulation (``repro.sim.noc.NocSim.simulate_trace``) maps
        onto boundary serdes ports, and the scalar ``wire_bytes`` stays
        the sum of the streams.
        """
        ndev = self.plan.dp_size * self.plan.tp_size
        stats, _ = self.decode_wire_stats()
        prof = {"decode": {k: v * ndev
                           for k, v in sorted(stats.by_stream.items())}}
        if self.spec_k > 0:
            vstats, _ = self.verify_wire_stats(1.0)
            prof["verify"] = {k: v * ndev
                              for k, v in sorted(vstats.by_stream.items())}
        return prof

    def pool_stats(self) -> dict:
        """KV pool occupancy + bytes, next to the dense baseline.

        ``kv_bytes_dense`` is what the pre-paging layout reserved
        (every slot charged ``pages_per_slot`` pages up front) — the
        ``kv_bytes_pool``/``kv_bytes_dense`` ratio is the HBM the block
        table frees for more slots at equal hardware.  ``pressure`` is
        the fraction of the pool mapped or in limbo (1.0 = the next
        alloc-on-extend is at the mercy of preemption) — the signal SLO
        monitors trend per step.
        """
        alloc = self.cache.allocator
        return {
            "page_size": alloc.page_size,
            "num_pages": alloc.num_pages,
            "pages_in_use": alloc.pages_in_use,
            "pages_in_limbo": alloc.pages_in_limbo,
            "pressure": alloc.pressure,
            "peak_pages_in_use": self.cache.peak_pages_in_use,
            "kv_bytes_mapped": self.cache.kv_bytes_mapped(),
            "kv_bytes_pool": self.cache.kv_bytes_pool(),
            "kv_bytes_dense": self.cache.kv_bytes_dense_reservation(),
        }
