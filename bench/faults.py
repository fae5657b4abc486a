"""Faults a serving cell can have, planted under the timed path.

Each one breaks the program where a later change could break it, before
the engine is built, so that the programs the window drives carry it;
``correct`` has to come out false under every one
(``tests/bench/test_bench_rehearsal.py``, and ``control.py --fault`` at a
cell's own size on the chip).

``stale_pool``       a decode step that returns its state unchanged: the
                     KV rows of the token it decodes are never written to
                     the page pool, so later steps read whatever the pool
                     held there.
``token_off_by_one`` a token altered where it is produced: the sampler's
                     pick plus one.
``no_exchange``      the exchange between chips left out: every chip keeps
                     its own coded partial where the psum gathers and sums
                     all of them (``tp > 1`` only).
"""
from __future__ import annotations


def plant(name: str, setattr_=setattr) -> None:
    """Plant fault ``name``; ``setattr_`` lets a test undo it
    (``monkeypatch.setattr``)."""
    if name == "stale_pool":
        from repro.models import blocks_attn
        setattr_(blocks_attn, "_paged_kv_write",
                 lambda cache, *a, **kw: cache)
    elif name == "token_off_by_one":
        from repro.serving import sampling
        real = sampling.sample

        def off_by_one(logits, *a, **kw):
            width = logits.shape[-1] * kw.get("tp_size", 1)
            return (real(logits, *a, **kw) + 1) % width
        setattr_(sampling, "sample", off_by_one)
    elif name == "no_exchange":
        from repro.core import boundary
        setattr_(boundary, "coded_psum",
                 lambda x, params, codec, *a, **kw:
                 boundary._local_roundtrip(x, params, codec))
    else:
        raise ValueError(f"no fault {name!r}")


NAMES = ("stale_pool", "token_off_by_one", "no_exchange")
