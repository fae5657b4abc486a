"""How ``correct`` is decided: what the timed programs produced against the
plain reference.

Two things are read once the window has closed.

* Served tokens.  A sample of the finished requests, drawn from the seed
  and always holding the longest one, is run through
  ``reference.Reference`` over each prompt with its served tokens.  Per
  served token, the gap by which its reference logit lies below the
  reference's best at that position (0 where the program chose the
  reference's own first choice; greedy traffic only): ``mean_logit_gap``
  over all of them, and the widest, ``max_logit_gap``.  This covers every
  layer end to end, but the spike code makes it coarse: each boundary
  rounds to 15 levels, so rounding-order differences flip codes that
  compound over the layers, and a sound run lands a share of its tokens
  off the reference's first choice.
* The KV pool.  After the pipeline is drained, layer 0's keys and values
  of a sample of the live slots, read from the page pool through each
  slot's block table at every position the slot holds (the prompt's rows
  written by the prefill insert, the rest by decode steps), against the
  reference's: ``kv_rel_err``, the worst relative Frobenius error over the
  slots and over keys and values.  Layer 0 sits behind one boundary, so
  the comparison is fine where the served tokens' is coarse.

Each cell's traffic file names the readings it compares and their limits.
Besides, every finished request must hold exactly the tokens it asked for,
all in the vocabulary, and every request due in the window must have been
answered.
"""
from __future__ import annotations

import numpy as np

from bench.reference import Reference
from bench.weights import split_seed


def sample(records, seed: int, k: int) -> list:
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    done = sorted((r for r in records if r.tokens),
                  key=lambda r: (-len(r.tokens), r.item.rid))
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    rest = list(rng.permutation(len(done) - 1)[:k - 1] + 1)
    return [done[0]] + [done[i] for i in sorted(rest)]


def bad_outputs(records, vocab: int) -> int:
    """Finished requests whose tokens are not what they asked for."""
    return sum(1 for r in records if r.tokens is not None and (
        len(r.tokens) != r.item.max_new
        or any(not 0 <= t < vocab for t in r.tokens)))


def pool_rows(engine, seed: int, k: int) -> list:
    """``(tokens, n_prompt, keys, values)`` of layer 0 for the live slot
    holding the longest context and ``k - 1`` others drawn from the seed:
    every position the slot holds, read from the engine's page pool.
    Drains the engine's pipeline first, so what the host has committed is
    what the pool holds."""
    import jax.numpy as jnp
    engine.flush()
    live = []
    for i in engine.active_slots():
        st = engine._slots[i]
        n = engine._committed_pos(st)
        if st.live and st.pending_first is None and st.out \
                and int(engine._pos[i]) == n:
            live.append((-n, i, st))
    if not live:
        return []
    live.sort(key=lambda x: x[:2])
    rng = np.random.default_rng([seed, 11])
    rest = sorted(rng.permutation(len(live) - 1)[:k - 1] + 1)
    pool = engine.cache.buffers["pos0"]["kv"]  # [layer, page, offset, lanes]
    out = []
    for _, i, st in [live[0]] + [live[j] for j in rest]:
        seq = list(st.req.prompt) + list(st.out[:-1])
        # the whole block-table row (one gather shape for every slot);
        # rows past the slot's context are cut off below
        pages = jnp.asarray(np.maximum(engine.cache.block_table[i], 0))
        kv = [np.asarray(pool[name][0, pages], np.float32)
              .reshape(-1, pool[name].shape[-1])[:len(seq)]
              for name in ("k", "v")]
        out.append((seq, len(st.req.prompt), *kv))
    return out


def _rel_err(a, ref):
    return float(np.linalg.norm(a - ref) / max(np.linalg.norm(ref), 1e-30))


def read_reference(config: dict, seq_len: int, seed: int, picked, pool,
                   modes=()) -> dict:
    """Readings of the reference over the ``picked`` requests and the
    ``pool`` rows (``pool_rows``); for each of ``modes``
    (``reference.py``: ``"fp8"``, the control, or the ``"f32"`` witness)
    the same readings of that mode in the program's place, prefixed with
    its name: its own first choices at the served positions and its own
    layer-0 keys and values, all judged by the bfloat16 reference."""
    ref = Reference(config, seq_len)
    parts = split_seed(seed)
    names = ("",) + tuple(modes)
    gaps = {m: [] for m in names}
    for r in picked:
        for m in names:
            g, _ = ref.gaps(parts, r.item.prompt, r.tokens,
                            control=m or None)
            gaps[m].append(g)
    kv_err = {m: [] for m in names}
    for seq, n_prompt, k, v in pool:
        want = ref.kv0(parts, seq, n_prompt)
        for m in names:
            got = (k, v) if not m else ref.kv0(parts, seq, n_prompt, m)
            kv_err[m] += [_rel_err(a, b) for a, b in zip(got, want)]
    out = {"requests_checked": len(picked),
           "tokens_checked": sum(len(g) for g in gaps[""]),
           "slots_checked": len(pool)}
    for m in names:
        pre = f"{m}_" if m else ""
        allg = np.concatenate(gaps[m]) if gaps[m] else np.zeros(1)
        out[pre + "max_logit_gap"] = float(allg.max())
        out[pre + "mean_logit_gap"] = float(allg.mean())
        out[pre + "rms_logit_gap"] = float(np.sqrt((allg ** 2).mean()))
        out[pre + "p90_logit_gap"] = float(np.percentile(allg, 90))
        out[pre + "off_argmax_share"] = float((allg > 0).mean())
        out[pre + "kv_rel_err"] = max(kv_err[m], default=0.0)
    out["first_token_max_gap"] = max((float(g[0]) for g in gaps[""]),
                                     default=0.0)
    return out


def verdict(readings: dict, limits: dict, prefix: str = "") -> dict:
    """``{name: {"value", "limit"}}`` for each reading a cell compares;
    ``prefix`` judges a control mode's readings (``"fp8_"``) by the same
    limits."""
    return {name: {"value": readings[prefix + name], "limit": limit}
            for name, limit in limits.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
