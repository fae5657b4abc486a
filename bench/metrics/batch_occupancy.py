"""Mean share of the slots holding a request, over the window's steps."""
from bench import readings as R


def read(rec):
    steps = R.window_steps(rec)
    if not steps:
        return None
    return 100.0 * sum(s[1] for s in steps) / (len(steps) * rec.slots)
