"""Device self time of the ops under the spike_exchange named scope (each cross-chip exchange of core/boundary.py: encode, collective, decode and local sum) inside the decode steps, per step, mean over chips.  A fusion counts where its root's scope puts it, as for codec_ms_per_step; a program without the scope reads nothing."""
from bench import program_trace as PT
from bench import readings as R
from bench import reduce_trace as rt

#: the named scope ``core/boundary.py`` runs each cross-chip exchange under
SCOPE = "spike_exchange"


def _seconds_per_step(dev, prog, lo, hi):
    """Self time of the scoped ops inside the decode steps in ``[lo, hi]``
    on one chip, per step; None where no op of those steps carries the
    scope."""
    steps = rt.decode_steps(dev, lo, hi)
    total, seen = 0.0, False
    for m in steps:
        names = prog.op_names(m.name)
        for op, t in rt.self_times(rt.within(dev.ops, m.start,
                                             m.end)).items():
            if SCOPE in names.get(op, "").split("/"):
                total += t
                seen = True
    return total / len(steps) if seen else None


def read(rec):
    if R.traced(rec) is None:
        return None
    prog = PT.of(rec)
    return R.ms(R.per_device_mean(
        rec, lambda d, lo, hi: _seconds_per_step(d, prog, lo, hi)))
