"""Share of the traced window in which no op ran on the device, mean over chips."""
from bench import readings as R


def read(rec):
    return R.idle_share(rec)
