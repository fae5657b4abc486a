"""Device self time of the ops under the kv_insert named scope (the admission's KV insert into the page pool, serving/kv_cache.py: the gather over tp and the page copies) in the traced window, per engine.insert span there, mean over chips.  A fusion counts where its root's scope puts it; a program without the scope reads nothing."""
from bench import program_trace as PT
from bench import readings as R
from bench import reduce_trace as rt

#: the named scope ``serving/kv_cache.py`` runs the insert's body under
SCOPE = "kv_insert"


def _scoped(op_name: str) -> bool:
    return SCOPE in op_name.split("/")


def _seconds(dev, prog, lo, hi, scoped_runs: dict):
    """Self time of the scoped ops of the program runs in ``[lo, hi]`` on
    one chip; None where no op there carries the scope.  ``scoped_runs``
    keeps, per program run name, whether its HLO has the scope at all."""
    total, seen = 0.0, False
    for m in rt.within(dev.modules, lo, hi):
        names = prog.op_names(m.name)
        if m.name not in scoped_runs:
            scoped_runs[m.name] = any(_scoped(v) for v in names.values())
        if not scoped_runs[m.name]:
            continue
        for op, t in rt.self_times(rt.within(dev.ops, m.start,
                                             m.end)).items():
            if _scoped(names.get(op, "")):
                total += t
                seen = True
    return total if seen else None


def read(rec):
    t = R.traced(rec)
    if t is None:
        return None
    _, lo, hi = t
    prog = PT.of(rec)
    admits = len(PT.in_window(prog.spans, lo, hi, "engine.insert"))
    if not admits:
        return None
    scoped_runs: dict = {}

    def per_admit(d, lo, hi):
        s = _seconds(d, prog, lo, hi, scoped_runs)
        return None if s is None else s / admits

    return R.ms(R.per_device_mean(rec, per_admit))
