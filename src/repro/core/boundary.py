"""Spike-coded boundary collectives — the paper's die-to-die interface on TPU.

Every tensor that crosses a chip boundary on TPU moves through a
collective.  ``BoundaryCodec`` wraps the four collectives the framework
uses (all_gather / psum_scatter / ppermute / all_to_all) so that the bytes
on the ICI wire are spike counts (int8, or packed uint4) instead of
bf16/f32 activations.  Modes:

  none        : plain bf16 collective (the ANN baseline).
  int8        : per-channel absmax int8 quantization (ablation baseline).
  spike       : paper-faithful — T-tick LIF (lax.scan) per boundary, int8
                signed counts on the wire. 2x fewer bytes than bf16.
  spike_fused : closed-form count encoder (bit-identical wire for the
                deterministic rate code), no T-tick scan. 2x bytes.
  spike_pack4 : fused encoder with T<=7, two counts per byte. 4x bytes.
  sparse_topk : event-driven packets — fixed-capacity (index,count) pairs
                for the top-c fraction of active channels (beyond-paper;
                DESIGN.md §2). ~(3..5)/ (2*c) x reduction.

Gradients: the wire is integer, so each boundary is a ``jax.custom_vjp``
whose forward runs the integer collective and whose backward runs the
transpose collective on the (optionally compressed) cotangent, chained
through the local encode/decode VJP (surrogate LIF gradients + straight-
through rounding from ``repro.core.spike``).

All functions must be called inside ``shard_map`` with the named axes
bound.  The channel axis is the last axis; ``axis`` selects the token
axis being gathered/scattered.

Every cross-chip exchange — encode, the collective, decode and the
local sum — runs under the named scope ``spike_exchange``, and the
codec's local ops inside it under ``spike_codec`` (with ``encode`` /
``decode`` sub-scopes where the two stay apart), so their HLO
``op_name`` metadata — and the device trace — can tell the exchange's
time, and the codec's share of it, from the rest of a step.  The
collectives stay outside ``spike_codec``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from . import spike
from .spike import SpikeConfig

Axis = Any  # str | tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class BoundaryCodec:
    """Static description of one class of boundary."""

    mode: str = "none"
    cfg: SpikeConfig = SpikeConfig()
    capacity: float = 0.125        # sparse_topk capacity fraction
    bwd_mode: str = "none"         # compress backward wire too ("int8"|"none")

    def wire_bits(self) -> float:
        """Bits per boundary element on the wire (for roofline bookkeeping)."""
        if self.mode == "none":
            return 16.0
        if self.mode in ("int8", "spike", "spike_fused"):
            return 8.0
        if self.mode == "spike_pack4":
            return 4.0
        if self.mode == "sparse_topk":
            return self.capacity * (8 + 32)
        raise ValueError(self.mode)


ANN = BoundaryCodec(mode="none")
HNN_FAITHFUL = BoundaryCodec(mode="spike", cfg=SpikeConfig(T=15, faithful=True))
HNN_FUSED = BoundaryCodec(mode="spike_fused", cfg=SpikeConfig(T=15))
HNN_PACK4 = BoundaryCodec(mode="spike_pack4", cfg=SpikeConfig(T=7))


#: named scope of the codec's local ops; it holds none of the stream
#: hints of ``launch.roofline``, so no collective's stream changes
CODEC_SCOPE = "spike_codec"


#: named scope of each whole cross-chip exchange (``_exchange``); like
#: the codec's, it holds no stream hint of ``launch.roofline``
EXCHANGE_SCOPE = "spike_exchange"


def _exchange(fn):
    """Run ``fn``, a cross-chip exchange, under ``EXCHANGE_SCOPE``."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with jax.named_scope(EXCHANGE_SCOPE):
            return fn(*args, **kwargs)
    return scoped


@contextlib.contextmanager
def _codec_scope(part: str):
    """``spike_codec/<part>`` (``encode`` or ``decode``) around codec ops."""
    with jax.named_scope(CODEC_SCOPE), jax.named_scope(part):
        yield


def _axis_size(axis_name: Axis) -> int:
    if isinstance(axis_name, (tuple, list)):
        n = 1
        for a in axis_name:
            n *= lax.axis_size(a)
        return n
    return lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# local encode/decode to the integer wire format
# ---------------------------------------------------------------------------


def _encode_local(x, params, codec: BoundaryCodec):
    """x float [..., C] -> (wire int tensor, decode closure, counts float)."""
    with _codec_scope("encode"):
        cfg = codec.cfg
        if codec.mode == "int8":
            amax = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)),
                           keepdims=True)
            s = jnp.maximum(amax, 1e-6) / 127.0
            wire = jnp.round(x / s).astype(jnp.int8)
            return wire, s, None
        counts = spike.encode(x, params, cfg)           # float in {-T..T}
        if codec.mode == "spike_pack4":
            wire = (counts + cfg.T).astype(jnp.uint8)   # {0..14} fits 4 bits
            shp = wire.shape
            wire = spike.pack4(wire.reshape(-1, shp[-1])).reshape(
                *shp[:-1], shp[-1] // 2)
            return wire, None, counts
        wire = counts.astype(jnp.int8)
        return wire, None, counts


def _decode_local(wire, params, codec: BoundaryCodec, scale_i8, dtype):
    # decode directly in the compute dtype: counts are small integers,
    # exactly representable in bf16, and the f32 intermediate would be the
    # largest transient buffer at the boundary
    with _codec_scope("decode"):
        cfg = codec.cfg
        if codec.mode == "int8":
            return (wire.astype(jnp.float32) * scale_i8).astype(dtype)
        if codec.mode == "spike_pack4":
            shp = wire.shape
            u = spike.unpack4(wire.reshape(-1, shp[-1])).reshape(
                *shp[:-1], shp[-1] * 2)
            counts = u.astype(dtype) - jnp.asarray(cfg.T, dtype)
        else:
            counts = wire.astype(dtype)
        return spike.decode(counts, params, cfg, dtype)


def _local_roundtrip(x, params, codec: BoundaryCodec):
    """Differentiable local view of encode->wire->decode (for the VJP)."""
    if codec.mode == "int8":
        with jax.named_scope(CODEC_SCOPE):
            amax = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)),
                           keepdims=True)
            s = jnp.maximum(amax, 1e-6) / 127.0
            return spike.round_ste(x / s) * s
    with _codec_scope("encode"):
        counts = spike.encode(x, params, codec.cfg)
    with _codec_scope("decode"):
        return spike.decode(counts, params, codec.cfg, x.dtype)


# ---------------------------------------------------------------------------
# sparsity statistics (feeds the eq-10 regularizer)
# ---------------------------------------------------------------------------


def boundary_penalty(x, params, codec: BoundaryCodec):
    """Differentiable sparsity penalty + firing-rate stat for one boundary."""
    if codec.mode in ("none", "int8"):
        return jnp.zeros((), x.dtype), jnp.zeros((), x.dtype)
    counts = spike.encode(x, params, codec.cfg)
    pen = spike.sparsity_loss(counts, codec.cfg.T, codec.cfg.target_rate,
                              codec.cfg.lam)
    occ = spike.occupancy(counts)
    return pen.astype(x.dtype), occ.astype(x.dtype)



def _roundtrip_bwd(x, theta, log_scale, g, codec: BoundaryCodec):
    """Analytic VJP of the local encode->decode roundtrip (no saved
    linearization residuals; see spike.roundtrip_vjp)."""
    if codec.mode == "int8":
        # straight-through within the absmax clip; no learnable params
        return (g.astype(x.dtype), jnp.zeros_like(theta),
                jnp.zeros_like(log_scale))
    return spike.roundtrip_vjp(x, theta, log_scale, g, codec.cfg)


# ---------------------------------------------------------------------------
# coded all_gather (tiled, along token axis)
# ---------------------------------------------------------------------------


@_exchange
def coded_all_gather(x, params, codec: BoundaryCodec, axis_name: Axis,
                     axis: int = 0):
    """Gather token-sharded activations across ``axis_name``; spike wire."""
    if codec.mode == "none":
        return lax.all_gather(x, axis_name, axis=axis, tiled=True)

    if codec.mode == "sparse_topk":
        return _topk_all_gather(x, params, codec, axis_name, axis)

    @jax.custom_vjp
    def _ag(x, theta, log_scale):
        p = {"theta": theta, "log_scale": log_scale}
        wire, s8, _ = _encode_local(x, p, codec)
        wire_g = lax.all_gather(wire, axis_name, axis=axis, tiled=True)
        if s8 is not None:
            # per-source-chip scales: decode segment-wise
            n = _axis_size(axis_name)
            s8_g = lax.all_gather(s8, axis_name, axis=0, tiled=False)  # [n,1..,C]
            with _codec_scope("decode"):
                seg = jnp.moveaxis(
                    wire_g.reshape(wire_g.shape[:axis]
                                   + (n, wire_g.shape[axis] // n)
                                   + wire_g.shape[axis + 1:]), axis, 0)
                dec = seg.astype(jnp.float32) * s8_g.reshape(
                    (n,) + (1,) * (seg.ndim - 2) + (s8.shape[-1],))
                dec = jnp.moveaxis(dec, 0, axis)
                return dec.reshape(wire_g.shape).astype(x.dtype)
        return _decode_local(wire_g, p, codec, None, x.dtype)

    def _fwd(x, theta, log_scale):
        # save primals only; the local-roundtrip VJP is recomputed in _bwd
        # (linearization residuals at [B,S,D] width dominate backward HBM)
        return _ag(x, theta, log_scale), (x, theta, log_scale)

    def _bwd(res, g):
        x, theta, log_scale = res
        if codec.bwd_mode == "int8":
            dummy = {"theta": theta, "log_scale": log_scale}
            g_loc = coded_psum_scatter(g, dummy,
                                       BoundaryCodec(mode="int8"),
                                       axis_name, axis=axis)
        else:
            g_loc = lax.psum_scatter(g, axis_name, scatter_dimension=axis,
                                     tiled=True)
        return _roundtrip_bwd(x, theta, log_scale, g_loc, codec)

    _ag.defvjp(_fwd, _bwd)
    return _ag(x, params["theta"], params["log_scale"])


# ---------------------------------------------------------------------------
# coded psum_scatter: sum of per-chip spike counts = CLP accumulate (§3.5)
# ---------------------------------------------------------------------------


@_exchange
def coded_psum_scatter(x, params, codec: BoundaryCodec, axis_name: Axis,
                       axis: int = 0):
    """Reduce-scatter partial sums across ``axis_name``.

    Coded modes move int8 counts with an all_to_all and accumulate the
    decoded counts locally (the paper's spike-accumulation, eq 3) —
    identical wire bytes to a reduce-scatter, no int8-overflow hazard.
    """
    if codec.mode == "none":
        return lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)

    n = _axis_size(axis_name)

    @jax.custom_vjp
    def _ps(x, theta, log_scale):
        p = {"theta": theta, "log_scale": log_scale}
        wire, s8, _ = _encode_local(x, p, codec)
        # split the token axis into n chunks, exchange, sum decoded chunks
        w = _split_axis(wire, n, axis)           # [n, ..., tok/n, ..., C]
        w = _a2a(w, axis_name)                   # recv one chunk per peer
        if s8 is not None:
            s8 = lax.all_gather(s8, axis_name, axis=0)   # [n, 1.., C]
            dec = _decode_local(w, p, codec, s8, x.dtype)
        else:
            dec = _decode_local(w, p, codec, None, x.dtype)
        return jnp.sum(dec, axis=0)

    def _fwd(x, theta, log_scale):
        return _ps(x, theta, log_scale), (x, theta, log_scale)

    def _bwd(res, g):
        x, theta, log_scale = res
        if codec.bwd_mode == "int8":
            dummy = {"theta": theta, "log_scale": log_scale}
            gg = coded_all_gather(g, dummy, BoundaryCodec(mode="int8"),
                                  axis_name, axis=axis)
        else:
            gg = lax.all_gather(g, axis_name, axis=axis, tiled=True)
        return _roundtrip_bwd(x, theta, log_scale, gg, codec)

    _ps.defvjp(_fwd, _bwd)
    return _ps(x, params["theta"], params["log_scale"])


def _split_axis(x, n, axis):
    """[..., tok, ...] -> [n, ..., tok/n, ...] splitting ``axis``."""
    shp = list(x.shape)
    assert shp[axis] % n == 0, (shp, n, axis)
    new = shp[:axis] + [n, shp[axis] // n] + shp[axis + 1:]
    x = x.reshape(new)
    return jnp.moveaxis(x, axis, 0)


def _a2a(x, axis_name):
    """all_to_all over leading split dim (handles tuple axis names)."""
    if isinstance(axis_name, (tuple, list)) and len(axis_name) > 1:
        # decompose: successive all_to_alls over each axis
        sizes = [lax.axis_size(a) for a in axis_name]
        n = x.shape[0]
        out = x
        # reshape leading dim [n] -> sizes, a2a each axis in turn
        out = out.reshape(tuple(sizes) + x.shape[1:])
        for i, a in enumerate(axis_name):
            out = lax.all_to_all(out, a, split_axis=i, concat_axis=i,
                                 tiled=False)
        return out.reshape((n,) + x.shape[1:])
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)


# ---------------------------------------------------------------------------
# decode-path boundaries (token-replicated activations)
# ---------------------------------------------------------------------------
#
# At serving time activations are [B, 1, D] and token-REPLICATED over tp
# (every rank holds every slot's token), so the decode path has two
# boundary shapes the training collectives don't cover:
#
#   coded_psum      : all-reduce of per-rank partial sums whose wire is
#                     the coded format (spike accumulation, eq 3).
#   wire_roundtrip  : a die-to-die hop with no collective at all — the
#                     tensor is already replicated, but it still crosses
#                     the spike interface, so it is encoded/decoded
#                     locally.  This keeps decode numerics identical to
#                     the coded gather that train/prefill apply to the
#                     same boundary.
#
# Both are careful to stay BATCH-INDEPENDENT: no reduction mixes slots,
# and int8 scales are per-token.  This is the invariant that makes
# batched continuous decode produce token-for-token the same output as
# single-request decode (tests/dist_scenarios.py::serving_parity).


def wire_roundtrip(x, params, codec: BoundaryCodec):
    """Local encode->wire->decode for a replicated decode activation."""
    if codec.mode == "none":
        return x
    if codec.mode == "int8":
        # per-token scale (NOT per-channel-over-batch): decode slots must
        # not see each other's magnitudes
        with jax.named_scope(CODEC_SCOPE):
            s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                            1e-6) / 127.0
            return (spike.round_ste(x / s) * s).astype(x.dtype)
    if codec.mode == "sparse_topk":
        C = x.shape[-1]
        k = min(max(8, int(C * codec.capacity)), C)
        with _codec_scope("encode"):
            c = spike.encode(x, params, codec.cfg)
            mag = lax.stop_gradient(jnp.abs(c))
            thresh = jnp.sort(mag, axis=-1)[..., C - k][..., None]
            mask = (mag >= thresh).astype(c.dtype)
        with _codec_scope("decode"):
            return spike.decode(c * mask, params, codec.cfg, x.dtype)
    return _local_roundtrip(x, params, codec)


@_exchange
def coded_psum(x, params, codec: BoundaryCodec, axis_name: Axis):
    """All-reduce partial sums across ``axis_name``; coded wire.

    Each rank encodes its partial to the wire format, the int counts are
    exchanged (all_gather of the wire tensor), and every rank decodes and
    sums the peer contributions locally — the paper's spike-accumulation
    semantics, matching ``coded_psum_scatter`` per element so decode and
    train/prefill see the same boundary numerics.  ``sparse_topk`` falls
    back to dense counts on this path (decode tensors are [B,1,D]-tiny).
    """
    if codec.mode == "none":
        return lax.psum(x, axis_name)

    @jax.custom_vjp
    def _pr(x, theta, log_scale):
        p = {"theta": theta, "log_scale": log_scale}
        if codec.mode == "int8":
            with _codec_scope("encode"):
                s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                                1e-6) / 127.0
                wire = jnp.round(x / s).astype(jnp.int8)
            wire_g = lax.all_gather(wire, axis_name, axis=0, tiled=False)
            s_g = lax.all_gather(s, axis_name, axis=0, tiled=False)
            with _codec_scope("decode"):
                dec = wire_g.astype(jnp.float32) * s_g.astype(jnp.float32)
            return jnp.sum(dec, axis=0).astype(x.dtype)
        if codec.mode == "sparse_topk":
            with _codec_scope("encode"):
                wire = spike.encode(x, p, codec.cfg).astype(jnp.int8)
            wire_g = lax.all_gather(wire, axis_name, axis=0, tiled=False)
            with _codec_scope("decode"):
                dec = spike.decode(wire_g.astype(x.dtype), p, codec.cfg,
                                   x.dtype)
            return jnp.sum(dec, axis=0)
        wire, _, _ = _encode_local(x, p, codec)
        wire_g = lax.all_gather(wire, axis_name, axis=0, tiled=False)
        dec = _decode_local(wire_g, p, codec, None, x.dtype)
        return jnp.sum(dec, axis=0)

    def _fwd(x, theta, log_scale):
        return _pr(x, theta, log_scale), (x, theta, log_scale)

    def _bwd(res, g):
        # psum's cotangent is already replicated across the axis; each
        # rank backprops it through its local encode/decode roundtrip
        x, theta, log_scale = res
        return _roundtrip_bwd(x, theta, log_scale, g, codec)

    _pr.defvjp(_fwd, _bwd)
    return _pr(x, params["theta"], params["log_scale"])


# ---------------------------------------------------------------------------
# decode-step head-space boundaries (q/kv gathers + attention combine)
# ---------------------------------------------------------------------------
#
# The decode/verify attention step crosses the die boundary three more
# times than the D-space activations above: the q/kv HEAD gathers before
# the sharded flash partial, and the LSE-weighted combine of the
# partials after it.  These tensors live in head space ([B, K1, H, dh])
# where no learned spike params exist (theta/log_scale are per-channel
# over D), so every coded mode uses the params-free per-token int8
# absmax wire here — mode "none" stays plain fp.  The combine keeps the
# LSE scalars ([B, K1, Hq] f32) uncoded: they are O(heads) scalars, the
# one piece of decode-step traffic left at full precision.  Forward-only
# (serving); batch independence holds because every scale reduces over
# the channel axis only, never across slots.


@_exchange
def coded_head_all_gather(x, codec: BoundaryCodec, axis_name: Axis,
                          axis: int):
    """Gather head-sharded q/k/v across ``axis_name``; int8 wire when
    coded.  Scales ride the same gather (one per token x head), so each
    segment is decoded with its source shard's scale.  The named scope
    labels the collectives in HLO metadata so
    ``launch.roofline.parse_collectives`` can attribute their bytes to
    the ``head_all_gather`` packet stream."""
    with jax.named_scope("coded_head_all_gather"):
        if codec.mode == "none":
            return lax.all_gather(x, axis_name, axis=axis, tiled=True)
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-6) / 127.0
        wire = jnp.round(x / s).astype(jnp.int8)
        wire_g = lax.all_gather(wire, axis_name, axis=axis, tiled=True)
        s_g = lax.all_gather(s, axis_name, axis=axis, tiled=True)
        return (wire_g.astype(jnp.float32)
                * s_g.astype(jnp.float32)).astype(x.dtype)


def quantize_partial(o):
    """Per-token int8 absmax quantization of a locally-normalized
    attention partial ``[..., dh]`` -> ``(wire int8, scale f32)``.

    Bit-identical to the fused kernel's epilogue
    (``kernels.paged_decode``), so the reference gather path and the
    fused path put the same bytes on the wire.
    """
    o = o.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(o), axis=-1, keepdims=True),
                    1e-6) / 127.0
    return jnp.round(o / s).astype(jnp.int8), s


@_exchange
def coded_combine_partials(wire, scale, lse, axis_names: Axis, out_dtype):
    """LSE-weighted combine of int8-coded decode partials.

    The coded twin of ``models.common.combine_decode_partials``: each
    shard contributes its epilogue-quantized partial (``wire``/``scale``
    from the kernel or ``quantize_partial``) plus fp LSE; every rank
    gathers the wire bytes, decodes locally, and performs the weighted
    sum — spike-accumulation semantics, no fp partial on the wire.  The
    named scope tags all three gathers as the ``partial_combine`` packet
    stream for ``launch.roofline.parse_collectives``.
    """
    with jax.named_scope("coded_combine_partials"):
        wire_g = lax.all_gather(wire, axis_names, axis=0, tiled=False)
        s_g = lax.all_gather(scale, axis_names, axis=0, tiled=False)
        lse_g = lax.all_gather(lse, axis_names, axis=0, tiled=False)
        m = jnp.max(lse_g, axis=0)
        w = jnp.exp(lse_g - m)
        dec = wire_g.astype(jnp.float32) * s_g.astype(jnp.float32)
        o_sum = jnp.sum(dec * w[..., None], axis=0)
        l_sum = jnp.sum(w, axis=0)
        return (o_sum / jnp.maximum(l_sum[..., None], 1e-30)).astype(out_dtype)


# ---------------------------------------------------------------------------
# coded ppermute (pipeline-stage / pod-boundary sends)
# ---------------------------------------------------------------------------


@_exchange
def coded_ppermute(x, params, codec: BoundaryCodec, axis_name: str,
                   perm: Sequence[tuple[int, int]]):
    if codec.mode == "none":
        return lax.ppermute(x, axis_name, perm)

    inv_perm = [(d, s) for (s, d) in perm]

    @jax.custom_vjp
    def _pp(x, theta, log_scale):
        p = {"theta": theta, "log_scale": log_scale}
        wire, s8, _ = _encode_local(x, p, codec)
        wire = lax.ppermute(wire, axis_name, perm)
        if s8 is not None:
            s8 = lax.ppermute(s8, axis_name, perm)
        return _decode_local(wire, p, codec, s8, x.dtype)

    def _fwd(x, theta, log_scale):
        return _pp(x, theta, log_scale), (x, theta, log_scale)

    def _bwd(res, g):
        x, theta, log_scale = res
        gb = lax.ppermute(g, axis_name, inv_perm)
        return _roundtrip_bwd(x, theta, log_scale, gb, codec)

    _pp.defvjp(_fwd, _bwd)
    return _pp(x, params["theta"], params["log_scale"])


# ---------------------------------------------------------------------------
# coded KV migration (disaggregated prefill -> decode state handoff)
# ---------------------------------------------------------------------------
#
# Disaggregated serving migrates a finished prefill's paged KV from a
# prefill-role dp group to a decode-role group — the paper's wire
# discipline applied to STATE transfer, not just activations.  KV lives
# in head space ([.., pages, page_size, Hkv, dh]) where no learned
# spike params exist, so like the decode-step head boundaries above the
# coded wire here is params-free int8 absmax — but with POWER-OF-TWO
# scales (``kv_pow2_scale``): scale mul/div is then exact in floating
# point and the encode is idempotent (encode(decode(encode(x))) ==
# decode(encode(x)) bit-exactly), which is what lets a coded migration
# be lossless over pool values that were already coded once at insert.
# That idempotence is the disagg == colocated token-identity story for
# ``EngineConfig.kv_wire="coded"``: both topologies roundtrip the KV at
# admission, and the migration's re-encode of the roundtripped pool
# pages reproduces the wire bytes exactly.


def kv_pow2_scale(x):
    """Per-vector (last axis) absmax int8 scale, snapped to a power of 2.

    ``s = 2^k`` with ``k`` chosen from the frexp exponent of the absmax
    ``m`` so that ``m/s <= 127`` (and ``m/s > 63.5``, keeping at least
    ~7 significant bits): exact in fp arithmetic, no log2 rounding
    hazards.  A re-encode of ``round(x/s) * s`` recovers the identical
    ``s`` — see the section comment — because the decoded absmax is an
    integer multiple of a power of two.
    """
    m = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                            keepdims=True), 1e-6)
    frac, exp = jnp.frexp(m)
    k = jnp.where(frac > 127.0 / 128.0, exp - 6, exp - 7)
    return jnp.exp2(k.astype(jnp.float32))


def kv_wire_encode(x):
    """``x [..., dh] -> (wire int8, scale f32 [..., 1])`` — the coded KV
    handoff's wire format (pow2-absmax int8 per (position, head))."""
    s = kv_pow2_scale(x)
    wire = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return wire.astype(jnp.int8), s


def kv_wire_roundtrip(x):
    """Encode+decode ``x`` through the coded KV wire (lossy, idempotent).

    Applied at pool INSERT when ``EngineConfig.kv_wire="coded"`` — in
    the colocated AND the disaggregated engine alike — so the pool holds
    wire-representable values and a later coded migration is bit-exact.
    A 7-bit-mantissa value times a power-of-two scale is exactly
    representable in bf16 and f32, so the roundtrip is idempotent in
    either pool dtype.
    """
    wire, s = kv_wire_encode(x)
    return (wire.astype(jnp.float32) * s).astype(x.dtype)


def kv_wire_bytes(shape, dtype_bytes: int, coded: bool) -> int:
    """Wire bytes of ONE migrated KV staging buffer of ``shape``
    (``[..., dh]``): int8 counts + one f32 scale per dh-vector when
    coded, plain dtype bytes otherwise.  Host-side accounting only —
    ``SLOMonitor``/``emio_cost_from_trace`` price migrations with it."""
    n = 1
    for d in shape:
        n *= int(d)
    if not coded:
        return n * dtype_bytes
    return n + (n // int(shape[-1])) * 4


@_exchange
def coded_kv_migrate(x, codec: BoundaryCodec, axis_name: str,
                     perm: Sequence[tuple[int, int]]):
    """Send a paged-KV staging buffer ``x [..., dh]`` across the die
    boundary named ``axis_name`` along ``perm`` — the state-transfer
    sibling of ``coded_ppermute``.

    What rides CODED vs FP on the handoff:

    * KV page payload (this function, every attention ``kv`` /
      ``cross_kv`` leaf): pow2-absmax int8 — one int8 per element plus
      one f32 scale per (page, position, kv-head) dh-vector.  This is
      the O(prompt_len x Hkv x dh) bulk of the migration and the term
      the spike/int8 wire shrinks ~4x (bf16) to ~8x (f32 scales
      amortized over dh).
    * Recurrent/SSM state leaves (mamba/xLSTM/RWKV slot rows): FP via a
      plain ``lax.ppermute`` — they are O(1) per slot, carry
      log-space / accumulator values whose quantization would break
      greedy token identity, and are not worth coding.
    * Block-table / compacted page-list metadata: never on the device
      wire at all — the host allocator mirrors the mapping
      (``SlotAllocator.migrate_slot``), so only payload crosses.

    ``codec.mode == "none"`` sends plain fp (the ``kv_wire="fp"``
    default); every coded mode shares the one params-free int8 KV wire
    (KV is head-space — there are no learned theta/log_scale channels
    to spike against, exactly as at the decode-step head boundaries).
    Like every boundary collective, the wire/scale ppermute pair is
    what ``launch.roofline.parse_collectives`` sees, so the migration
    is priced like any other coded collective — and the named scope tags
    the ppermute pair as the ``kv_migrate`` packet stream.  Forward-only
    (serving).
    """
    with jax.named_scope("coded_kv_migrate"):
        if codec.mode == "none":
            return lax.ppermute(x, axis_name, perm)
        wire, s = kv_wire_encode(x)
        wire = lax.ppermute(wire, axis_name, perm)
        s = lax.ppermute(s, axis_name, perm)
        return (wire.astype(jnp.float32) * s).astype(x.dtype)


# ---------------------------------------------------------------------------
# coded all_to_all (MoE dispatch/combine)
# ---------------------------------------------------------------------------


@_exchange
def coded_all_to_all(x, params, codec: BoundaryCodec, axis_name: str,
                     split_axis: int, concat_axis: int):
    if codec.mode == "none":
        return lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    @jax.custom_vjp
    def _aa(x, theta, log_scale):
        p = {"theta": theta, "log_scale": log_scale}
        wire, s8, _ = _encode_local(x, p, codec)
        wire = lax.all_to_all(wire, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
        if s8 is not None:
            # segment-wise decode: chunks along concat_axis are per-source
            n = _axis_size(axis_name)
            s8_g = lax.all_gather(s8, axis_name, axis=0, tiled=False)
            seg = jnp.moveaxis(
                wire.reshape(wire.shape[:concat_axis]
                             + (n, wire.shape[concat_axis] // n)
                             + wire.shape[concat_axis + 1:]), concat_axis, 0)
            dec = seg.astype(jnp.float32) * s8_g.reshape(
                (n,) + (1,) * (seg.ndim - 2) + (s8.shape[-1],))
            dec = jnp.moveaxis(dec, 0, concat_axis)
            return dec.reshape(wire.shape).astype(x.dtype)
        return _decode_local(wire, p, codec, None, x.dtype)

    def _fwd(x, theta, log_scale):
        return _aa(x, theta, log_scale), (x, theta, log_scale)

    def _bwd(res, g):
        x, theta, log_scale = res
        gb = lax.all_to_all(g, axis_name, split_axis=concat_axis,
                            concat_axis=split_axis, tiled=True)
        return _roundtrip_bwd(x, theta, log_scale, gb, codec)

    _aa.defvjp(_fwd, _bwd)
    return _aa(x, params["theta"], params["log_scale"])


# ---------------------------------------------------------------------------
# sparse_topk: event-driven fixed-capacity packets (beyond-paper)
# ---------------------------------------------------------------------------


def _topk_all_gather(x, params, codec: BoundaryCodec, axis_name: Axis,
                     axis: int):
    """Send only the top-c fraction of |count| per token: (idx, count)."""
    cfg = codec.cfg
    C = x.shape[-1]
    k = max(8, int(C * codec.capacity))
    k = min(k, C)

    @jax.custom_vjp
    def _tk(x, theta, log_scale):
        p = {"theta": theta, "log_scale": log_scale}
        counts = spike.encode(x, p, cfg)
        mag = jnp.abs(counts)
        _, idx = lax.top_k(mag, k)                       # [..., k] int32
        vals = jnp.take_along_axis(counts, idx, axis=-1).astype(jnp.int8)
        idx_g = lax.all_gather(idx.astype(jnp.int32), axis_name,
                               axis=axis, tiled=True)
        val_g = lax.all_gather(vals, axis_name, axis=axis, tiled=True)
        out = jnp.zeros(val_g.shape[:-1] + (C,), jnp.float32)
        out = _scatter_last(out, idx_g, val_g.astype(jnp.float32))
        return spike.decode(out, p, cfg, x.dtype)

    def _local(a, t, l):
        p = {"theta": t, "log_scale": l}
        c = spike.encode(a, p, cfg)
        mag = jax.lax.stop_gradient(jnp.abs(c))
        thresh = jnp.sort(mag, axis=-1)[..., C - k][..., None]
        mask = (mag >= thresh).astype(c.dtype)
        return spike.decode(c * mask, p, cfg, a.dtype)

    def _fwd(x, theta, log_scale):
        return _tk(x, theta, log_scale), (x, theta, log_scale)

    def _bwd(res, g):
        x, theta, log_scale = res
        g_loc = lax.psum_scatter(g, axis_name, scatter_dimension=axis,
                                 tiled=True)
        _, vjp = jax.vjp(_local, x, theta, log_scale)
        return vjp(g_loc)

    _tk.defvjp(_fwd, _bwd)
    return _tk(x, params["theta"], params["log_scale"])


def _scatter_last(dense, idx, vals):
    """dense[..., idx[..., j]] = vals[..., j] along last axis."""
    return jax.vmap(lambda d, i, v: d.at[i].set(v))(
        dense.reshape(-1, dense.shape[-1]),
        idx.reshape(-1, idx.shape[-1]),
        vals.reshape(-1, vals.shape[-1]),
    ).reshape(dense.shape)
