"""Least time of the step's paged attention (bytes of the mapped K/V pages, queries and partials; score and value FLOPs) over the kernel's time."""
from bench import readings as R


def read(rec):
    return R.paged_attn_roofline(rec)
