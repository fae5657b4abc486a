"""Device time of one decode step (a run of the program that holds the paged-decode kernel), mean over the traced steps and chips."""
from bench import readings as R


def read(rec):
    return R.ms(R.decode_step_seconds(rec))
