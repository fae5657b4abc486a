"""Device time of the collective ops inside one decode step, mean over chips."""
from bench import readings as R


def read(rec):
    v = R.collective_seconds_per_step(rec)
    return R.ms(v) if v else None
