"""Output tokens committed in the window over the window, on the host clock."""
from bench import readings as R


def read(rec):
    steps = R.window_steps(rec)
    if not steps:
        return None
    return (steps[-1][3] - rec.tokens0) / (steps[-1][0] - rec.t0)
