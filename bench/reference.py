"""Plain reference of the served model, and its lower-precision control.

A straightforward forward pass of a Qwen1.5-style decoder (RMSNorm,
rotary attention with QKV bias, SwiGLU MLP) with the hybrid network's
spike-coded boundaries, written from the model's description; it imports
nothing of the program.  Weights are rebuilt from the seed layer by layer
inside the layer scan (``weights.draw_layer``), so the reference never
holds the model and takes nothing the program made.

The boundaries follow what the configuration states (``hnn_mode`` hnn,
codec ``spike_fused``, T ticks):

* spike round trip ``Q(x) = sign(x) * [|x| >= theta] * round(clip(|x| /
  s, 0, 1) * T) * (s / T)`` with ``s = exp(log_scale)``, per channel;
* every block input crosses a boundary: ``Q(norm(x))``;
* every block output is a sum over the ``tp`` chips of each chip's coded
  partial: ``sum_r Q(partial_r)``, chip r holding a contiguous slice of
  the heads (attention) or of the MLP columns;
* with ``tp > 1`` the embedding rows and the final hidden cross a
  boundary too;
* positions fed through decode steps (every position after the prompt's
  last; the prompt went through prefill) carry the per-(position, head)
  absmax int8 wire on the attention output, and with ``tp > 1`` on the
  queries, keys and values gathered across chips.  With ``tp > 1`` the
  program quantizes each chip's attention partial before the combine
  where this reference quantizes the combined output once: at most one
  int8 step per head apart.

Precision.  The configuration serves in bfloat16, and the spike code makes
that part of what the model computes: ``Q`` rounds to 15 levels, so a
value that lies within bfloat16's rounding of a level's edge lands on one
side in bfloat16 and on the other in float32, and such flips compound
over the layers (a float32 forward and a bfloat16 one of the same weights
choose different tokens; ``mode="f32"`` shows by how much).  So the
reference (``mode="bf16"``) keeps in bfloat16 every value the
configuration keeps in bfloat16 between operations (activations, K and V,
products, the codec's own arithmetic, whose step is bfloat16's rounding of
``s / T``), and computes each operation from those values in float32 at
the highest matmul precision, as a plain loop over layers.

``mode="fp8"`` is the control, the step below bfloat16: every value kept
between operations is kept in float8 e4m3 instead (each row scaled to the
format's range), and every product takes its operands rounded to float8
the same way (rows of the left, columns of the right).  Its logits are
read in bfloat16, so a pick is never decided by float8 ties.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.weights import draw, draw_layer, leaf_key, root_key
from bench.work import Dims

F32 = jnp.float32
BF16 = jnp.bfloat16
E4M3_MAX = 448.0
CHUNK = 256


def _q8(x, axis):
    s = E4M3_MAX / jnp.maximum(jnp.max(jnp.abs(x), axis=axis,
                                       keepdims=True), 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s


def _store(x, mode):
    """A value the configuration keeps between operations: in bfloat16,
    or, in the control, in float8 e4m3 scaled by the row's largest
    magnitude."""
    if mode == "fp8":
        return _q8(x, -1)
    return x if mode == "f32" else x.astype(BF16).astype(F32)


def _mm(a, b, mode):
    if mode == "fp8":
        a, b = _q8(a, -1), _q8(b, -2)
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def spike(x, theta, log_scale, ticks, mode):
    """The spike round trip in the codec's own arithmetic: bfloat16 where
    the configuration computes it in bfloat16."""
    dt = F32 if mode == "f32" else BF16
    x, theta = x.astype(dt), theta.astype(dt)
    s = jnp.exp(log_scale).astype(dt)
    mag = jnp.abs(x)
    c = jnp.round(jnp.clip(mag / s, 0.0, 1.0) * ticks)
    c = jnp.where(mag - theta >= 0, c, jnp.zeros_like(c))
    return (jnp.sign(x) * c * (s / ticks)).astype(F32)


def int8_roundtrip(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                    1e-6) / 127.0
    return jnp.round(x / s) * s


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = pos.astype(F32)[:, None, None] * inv
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


class Reference:
    """The reference for one configuration at one padded length."""

    def __init__(self, config: dict, seq_len: int):
        self.d = Dims.of(config)
        self.tp = int(config["tp"])
        self.ticks = float(config["spike_ticks"])
        self.theta = float(config["rope_theta"])
        self.eps = float(config["rms_norm_eps"])
        self.bias = bool(config["qkv_bias"])
        self.init = config.get("weights")
        self.S = seq_len
        if self.d.heads % self.tp or self.d.d_ff % self.tp:
            raise ValueError("heads and MLP width must split over tp")
        if seq_len % min(CHUNK, seq_len):
            raise ValueError(f"seq_len {seq_len} is not a multiple of {CHUNK}")
        self._gaps = jax.jit(self._gaps_fn,
                             static_argnames=("base", "control"))
        self._kv0 = jax.jit(
            lambda lo, hi, tokens, n_prompt, mode: self._hidden(
                root_key(lo, hi), tokens, n_prompt, mode, kv0=True),
            static_argnames=("mode",))

    # -- the model -------------------------------------------------------

    def _hidden(self, root, tokens, n_prompt, mode, kv0=False):
        """Final hidden states [S, D] as the mode keeps them, and the LM
        head [D, V]; with ``kv0``, layer 0's keys and values [S, H, dh]
        instead."""
        d, tp, S = self.d, self.tp, self.S
        D, H, dh, F = d.d_model, d.heads, d.head_dim, d.d_ff
        pos = jnp.arange(S)
        dec = (pos >= n_prompt)[:, None, None]        # fed by decode steps
        st = functools.partial(_store, mode=mode)

        def mm(a, b):
            return st(_mm(a, b, mode))

        def top(name, shape, dtype=BF16):
            return draw(leaf_key(root, name), name, shape, dtype,
                        self.init).astype(F32)

        def sp(prefix, layer=None):
            if layer is None:
                return (top(f"{prefix}/theta", (D,), F32),
                        top(f"{prefix}/log_scale", (D,), F32))
            return tuple(draw_layer(root, f"units/pos0/{prefix}/{n}", layer,
                                    (D,), F32, self.init)
                         for n in ("theta", "log_scale"))

        def Q(x, params):
            return spike(x, *params, self.ticks, mode)

        def norm(x, w):
            return st(rms_norm(x, w, self.eps))

        def silu(x):
            # the activation in the dtype it is served in, as a library
            # computes it there
            if mode == "f32":
                return jax.nn.silu(x)
            return jax.nn.silu(x.astype(BF16)).astype(F32)

        def attend(q, k, v):
            if mode == "fp8":
                s = jnp.stack([_mm(q[:, i], k[:, i].T, mode)
                               for i in range(H)])
            else:
                s = jnp.einsum("qhd,khd->hqk", q, k,
                               precision=lax.Precision.HIGHEST)
            s = s / jnp.sqrt(F32(dh))
            s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            if mode == "fp8":
                return jnp.stack([_mm(p[i], v[:, i], mode) for i in range(H)],
                                 axis=1)
            return jnp.einsum("hqk,khd->qhd", p, v,
                              precision=lax.Precision.HIGHEST)

        def w(name, shape, l, dtype=BF16):
            return draw_layer(root, f"units/pos0/{name}", l, shape, dtype,
                              self.init).astype(F32)

        def qkv(x, l):
            """Layer ``l``'s queries, keys and values, the keys and values
            as the KV pool holds them."""
            h = Q(norm(x, w("ln", (D,), l)), sp("sp_in", l))
            q, k, v = (mm(h, w(n, (D, H * dh), l))
                       for n in ("wq", "wk", "wv"))
            if self.bias:
                q = st(q + w("bq", (H * dh,), l))
                k = st(k + w("bk", (H * dh,), l))
                v = st(v + w("bv", (H * dh,), l))
            q, k, v = (t.reshape(S, H, dh) for t in (q, k, v))
            q, k = st(rope(q, pos, self.theta)), st(rope(k, pos, self.theta))
            if tp > 1:
                q, k, v = (st(jnp.where(dec, int8_roundtrip(t), t))
                           for t in (q, k, v))
            return q, k, v

        emb = top("embed", (d.vocab, D))
        x = emb[tokens]
        if tp > 1:
            x = Q(x, sp("sp_embed"))
        if kv0:
            return qkv(x, 0)[1:]

        def layer(x, l):
            q, k, v = qkv(x, l)
            o = attend(q, k, v)
            o = st(jnp.where(dec, int8_roundtrip(o), o)).reshape(S, H * dh)
            wo = w("wo", (H * dh, D), l)
            hs = H * dh // tp
            y = st(sum(Q(mm(o[:, r * hs:(r + 1) * hs],
                            wo[r * hs:(r + 1) * hs]), sp("sp_out", l))
                       for r in range(tp)))
            x = st(x + y)
            h = Q(norm(x, w("ln2", (D,), l)), sp("sp_in2", l))
            w1, w3, w2 = (w("w1", (D, F), l), w("w3", (D, F), l),
                          w("w2", (F, D), l))
            fs = F // tp

            def mlp(r):
                cols = slice(r * fs, (r + 1) * fs)
                a = silu(mm(h, w1[:, cols]))
                return Q(mm(st(a * mm(h, w3[:, cols])), w2[cols]),
                         sp("sp_out2", l))

            return st(x + st(sum(mlp(r) for r in range(tp)))), None

        x, _ = lax.scan(layer, x, jnp.arange(d.layers, dtype=jnp.int32))
        h = norm(x, top("final_ln", (D,)))
        if tp > 1:
            h = Q(h, sp("sp_head"))
        head = emb.T if d.tie else top("lm_head", (D, d.vocab))
        return h, head

    # -- the comparison --------------------------------------------------

    def _gaps_fn(self, lo, hi, tokens, targets, n_prompt, base, control):
        """Per position: how far the ``base`` mode's logit of ``targets``
        (or, with ``control`` naming another mode, of that mode's own
        first choice) lies below the base mode's best."""
        root = root_key(lo, hi)
        h, head = self._hidden(root, tokens, n_prompt, base)
        if control:
            hc, headc = self._hidden(root, tokens, n_prompt, control)
        c = min(CHUNK, self.S)

        def chunk(i):
            lg = _mm(lax.dynamic_slice_in_dim(h, i * c, c), head, "f32")
            if control:
                lc = _store(_mm(lax.dynamic_slice_in_dim(hc, i * c, c),
                                headc, control),
                            "f32" if control == "f32" else "bf16")
                pick = jnp.argmax(lc, axis=-1)
            else:
                pick = lax.dynamic_slice_in_dim(targets, i * c, c)
            at = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
            return jnp.max(lg, axis=-1) - at, pick

        gap, pick = lax.map(chunk, jnp.arange(self.S // c))
        return gap.reshape(-1), pick.reshape(-1)

    def gaps(self, seed_parts, prompt, served, control=None, base="bf16"):
        """Per served token of one request, how far the reference's logit
        of it lies below the reference's best (0 where it is the
        reference's own first choice), and the tokens compared; with
        ``control`` (``"fp8"``, or the ``"f32"`` witness) the same for that
        mode's own choices at the served positions."""
        P, n = len(prompt), len(served)
        seq = list(prompt) + list(served[:-1])
        if len(seq) > self.S:
            raise ValueError(f"sequence of {len(seq)} > {self.S}")
        tokens = np.zeros(self.S, np.int32)
        tokens[:len(seq)] = seq
        targets = np.zeros(self.S, np.int32)
        targets[P - 1:P - 1 + n] = served
        gap, pick = self._gaps(jnp.int32(seed_parts[0]),
                               jnp.int32(seed_parts[1]), tokens, targets,
                               jnp.int32(P), base=base, control=control)
        return (np.asarray(gap)[P - 1:P - 1 + n],
                np.asarray(pick)[P - 1:P - 1 + n])

    def kv0(self, seed_parts, seq, n_prompt, mode="bf16"):
        """Layer 0's keys and values ``[len(seq), H * dh]`` at every
        position of ``seq`` (the prompt, then the tokens decode steps fed),
        as the KV pool holds them; ``mode`` as for ``gaps``."""
        n = len(seq)
        if n > self.S:
            raise ValueError(f"sequence of {n} > {self.S}")
        tokens = np.zeros(self.S, np.int32)
        tokens[:n] = seq
        k, v = self._kv0(jnp.int32(seed_parts[0]), jnp.int32(seed_parts[1]),
                         tokens, jnp.int32(n_prompt), mode=mode)
        return (np.asarray(k, np.float32).reshape(self.S, -1)[:n],
                np.asarray(v, np.float32).reshape(self.S, -1)[:n])
