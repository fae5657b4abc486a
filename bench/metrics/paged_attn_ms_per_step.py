"""Device time of the paged-decode kernel per decode step (its calls in every layer), mean over chips."""
from bench import readings as R


def read(rec):
    return R.ms(R.kernel_seconds_per_step(rec))
