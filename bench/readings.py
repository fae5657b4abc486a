"""Computations the metric readers share.

A reader (``bench/metrics/<metric>.py``) defines ``read(rec)`` and
returns a number, or ``None`` when the run has nothing to read for it (no
trace, no completed request, no collective); the harness then leaves the
metric out of the result line.  ``rec`` is the run's record (see
``run.py``): the window on the host clock, the per-step counts, every
request's times, the trace reduction and the work arithmetic.
"""
from __future__ import annotations

import numpy as np

from bench import reduce_trace as rt
from bench import work


def ms(seconds):
    """Seconds to milliseconds, passing ``None`` through."""
    return None if seconds is None else 1000.0 * seconds


def p95(values):
    return float(np.percentile(values, 95)) if len(values) else None


def window_steps(rec) -> list:
    """Per-step records ``(t, active, pages, tokens)`` inside the window."""
    return [s for s in rec.steps if rec.t0 < s[0] <= rec.t_end]


def traced(rec):
    """(trace, lo, hi) or None in an untraced run."""
    if rec.trace is None or not rec.trace.spans:
        return None
    lo, hi = rec.trace.window
    return rec.trace, lo, hi


def per_device_mean(rec, fn):
    """Mean over chips of ``fn(device, lo, hi)``, skipping ``None``."""
    t = traced(rec)
    if t is None:
        return None
    tr, lo, hi = t
    vals = [v for v in (fn(d, lo, hi) for d in tr.devices) if v is not None]
    return float(np.mean(vals)) if vals else None


def decode_step_seconds(rec):
    def one(d, lo, hi):
        steps = rt.decode_steps(d, lo, hi)
        return np.mean([m.dur for m in steps]) if steps else None
    return per_device_mean(rec, one)


def kernel_seconds_per_step(rec):
    def one(d, lo, hi):
        steps = rt.decode_steps(d, lo, hi)
        if not steps:
            return None
        return rt.op_seconds(d, steps, rt.is_kernel) / len(steps)
    return per_device_mean(rec, one)


def collective_seconds_per_step(rec):
    def one(d, lo, hi):
        steps = rt.decode_steps(d, lo, hi)
        if not steps:
            return None
        return rt.op_seconds(d, steps, rt.is_collective) / len(steps)
    return per_device_mean(rec, one)


def traced_pages(rec):
    """Mean pages mapped over the steps the host ran in the traced
    window."""
    if traced(rec) is None:
        return None
    i, j = rec.traced_steps
    p = [s[2] for s in rec.steps[i:j]]
    return float(np.mean(p)) if p else None


def paged_attn_roofline(rec):
    """Least time of one decode step's paged attention on one chip (the
    K/V of the mapped pages and the queries and partials; the score and
    value products) over the kernel's measured time per step."""
    k = kernel_seconds_per_step(rec)
    pages = traced_pages(rec)
    if not k or pages is None or rec.peak is None:
        return None
    d, psz = rec.dims, rec.page_size
    nbytes = work.paged_attn_bytes(d, pages, psz, rec.slots, rec.chips)
    flops = work.attention_flops(d, pages * psz)
    least = work.roofline_seconds(flops / rec.chips, nbytes / rec.chips,
                                  rec.peak)
    return 100.0 * least / k


def decode_mfu(rec):
    """Model FLOPs of the tokens committed in the window (2 x weights per
    token, plus attention over each step's mapped context) over the
    window times the chips' bf16 peak."""
    steps = window_steps(rec)
    if len(steps) < 2 or rec.peak is None:
        return None
    flops, prev = 0, steps[0]
    for s in steps[1:]:
        flops += work.decode_flops(rec.dims, s[3] - prev[3],
                                   s[2] * rec.page_size)
        prev = s
    span = steps[-1][0] - steps[0][0]
    return 100.0 * flops / (span * rec.chips * rec.peak["bf16_flops_per_s"])


def idle_share(rec):
    t = traced(rec)
    if t is None or not t[0].devices:
        return None
    tr, lo, hi = t
    busy = np.mean([rt.busy_seconds(d, lo, hi) for d in tr.devices])
    return 100.0 * (1.0 - busy / (hi - lo))
