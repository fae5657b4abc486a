"""Device time of one prefill call (the engine's prefill program), mean over the traced calls and chips."""
from bench import readings as R
from bench import reduce_trace as rt


def read(rec):
    def one(d, lo, hi):
        runs = rt.prefill_steps(d, lo, hi)
        return sum(m.dur for m in runs) / len(runs) if runs else None
    return R.ms(R.per_device_mean(rec, one))
