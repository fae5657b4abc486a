"""95th percentile, over every request due in the window, of due time to admission into a slot."""
from bench import readings as R


def read(rec):
    return R.ms(R.p95([(r.admitted if r.admitted is not None else rec.t_wait)
                      - r.due for r in rec.due_in_window]))
