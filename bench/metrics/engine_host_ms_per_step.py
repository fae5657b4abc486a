"""Host time of one scheduler tick less its wait on the device: mean over the engine.step spans in the traced window of the span's time minus its engine.commit.wait time.  Also lists on standard error the traced window's ten longest device idle gaps, each named by the innermost engine.* span open at its middle."""
import sys

from bench import program_trace as PT
from bench import readings as R


def read(rec):
    t = R.traced(rec)
    if t is None:
        return None
    tr, lo, hi = t
    prog = PT.of(rec)
    if not prog.spans:
        return None
    if tr.devices:
        PT.print_engine_gaps(tr, prog, sys.stderr)
    return R.ms(PT.host_seconds_per_step(prog, lo, hi))
