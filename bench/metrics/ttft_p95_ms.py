"""95th percentile, over every request due in the window, of due time to first token on the host; a request that never got one counts with the whole wait."""
from bench import readings as R


def read(rec):
    return R.ms(R.p95([(r.first if r.first is not None else rec.t_wait)
                      - r.due for r in rec.due_in_window]))
