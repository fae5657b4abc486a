"""Device time of one decode step in the chat cell (same reading as decode_step_ms)."""
from bench import readings as R


def read(rec):
    return R.ms(R.decode_step_seconds(rec))
