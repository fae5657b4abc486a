"""Host bytes handed to the device per device step in the traced window: the bytes of the engine.stage and engine.prefill spans over the engine.launch spans, in kB of 1000 bytes."""
from bench import program_trace as PT
from bench import readings as R


def read(rec):
    t = R.traced(rec)
    if t is None:
        return None
    _, lo, hi = t
    b = PT.staged_bytes_per_step(PT.of(rec), lo, hi)
    return None if b is None else b / 1000.0
