"""Model FLOPs of the window's decode tokens over window x chips x bf16 peak."""
from bench import readings as R


def read(rec):
    return R.decode_mfu(rec)
