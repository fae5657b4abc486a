"""Device self time of the ops under the spike_codec named scope inside the decode steps, per step, mean over chips.  A fusion counts where its root's scope puts it: codec work fused into a neighbour whose root lies outside the scope is not counted, and a neighbour's work fused under a codec root is."""
from bench import program_trace as PT
from bench import readings as R


def read(rec):
    if R.traced(rec) is None:
        return None
    prog = PT.of(rec)
    return R.ms(R.per_device_mean(
        rec, lambda d, lo, hi: PT.codec_seconds_per_step(d, prog, lo, hi)))
