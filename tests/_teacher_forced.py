"""Teacher-forced oracle for the serving engine's token streams.

Each request's prompt plus its served tokens runs through the
full-sequence forward (``launch.serve.make_logits_step``) in one
teacher-forced pass; every served token's logit must then lie within
``TOL`` of the maximum of its row — the logit gap ``bench/check.py``
reports, held near zero here because the test configs are f32 and
greedy.  That forward computes attention on its own (no page pool, no
paged-decode kernel, no block tables), so it checks the engine's whole
decode wiring: window, softcap, RoPE positions, head gathers, codec.

Coded codecs on one device: the engine's decode and verify steps round
the attention output of every position they compute to the per-token
int8 wire (``boundary.quantize_partial``, fused into the kernel's
epilogue), even where the combine crosses no chip; the prefill does not.
With ``round_decoded=True`` the oracle applies the same rounding to the
attention output at every position past the prompt, so it stays exact
where one rounded level flips a spike code downstream.
"""
import contextlib
from unittest import mock

import numpy as np

TOL = 1e-4


@contextlib.contextmanager
def _rounded_attention(decoded):
    """Round ``common.flash_attention``'s output where ``decoded`` [B, S]
    is True, for the forwards traced inside the block."""
    import jax.numpy as jnp
    from repro.core import boundary
    from repro.models import common

    flash = common.flash_attention

    def rounded(*args, **kwargs):
        out = flash(*args, **kwargs)
        wire, scale = boundary.quantize_partial(out)
        dec = (wire.astype(jnp.float32) * scale).astype(out.dtype)
        return jnp.where(jnp.asarray(decoded)[:, :, None, None], dec, out)

    with mock.patch.object(common, "flash_attention", rounded):
        yield


def teacher_forced_logits(cfg, mesh, seq_len: int, batch: int,
                          round_decoded: bool = False):
    """``logits(params, prompts, outs) -> [len(prompts), seq_len, vocab]``
    f32 over the rows ``prompt + out`` (cut to ``seq_len``, zero-padded),
    ``batch`` rows per forward.  ``round_decoded`` (one-device meshes
    only): round the attention output past each prompt as the coded
    decode path does, compiling one forward per call."""
    import jax.numpy as jnp
    from repro.configs.base import ShapeCell
    from repro.launch import serve as SV, specs as SP

    plan = SP.make_plan(cfg, ShapeCell("tf", seq_len, batch, "train"), mesh)
    if round_decoded:
        assert mesh.size == 1, "the rounding is emulated on one device"
    fn = None if round_decoded else SV.make_logits_step(cfg, plan, mesh)

    def logits(params, prompts, outs):
        out = []
        for lo in range(0, len(prompts), batch):
            rows = list(zip(prompts, outs))[lo:lo + batch]
            toks = np.zeros((batch, seq_len), np.int32)
            decoded = np.zeros((batch, seq_len), bool)
            for i, (p, o) in enumerate(rows):
                s = (list(p) + list(o))[:seq_len]
                toks[i, :len(s)] = s
                decoded[i, len(p):] = True
            t = jnp.asarray(toks)
            if round_decoded:
                with _rounded_attention(decoded):
                    lg = SV.make_logits_step(cfg, plan, mesh)(
                        params, {"tokens": t, "labels": t})
            else:
                lg = fn(params, {"tokens": t, "labels": t})
            out.append(np.asarray(lg, np.float32)[:len(rows), :,
                                                  :cfg.vocab])
        return np.concatenate(out)

    return logits


def served_gaps(logits, params, prompts, outs):
    """Per request, each served token's gap below its row's maximum."""
    lg = logits(params, prompts, outs)
    gaps = []
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = lg[i, len(p) - 1:len(p) - 1 + len(o)]
        gaps.append(rows.max(-1) - rows[np.arange(len(o)), o])
    return gaps


def assert_on_teacher_forced(logits, params, prompts, outs, what=()):
    """Every request served at least one token, and each served token
    is the teacher-forced argmax within ``TOL``."""
    for i, g in enumerate(served_gaps(logits, params, prompts, outs)):
        assert len(g) > 0, (what, i)
        assert float(g.max()) <= TOL, (what, i, list(outs[i]), g)
