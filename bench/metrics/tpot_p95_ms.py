"""95th percentile, over the requests finished in the window with two tokens or more, of (last token - first token) / (tokens - 1)."""
from bench import readings as R


def read(rec):
    return R.ms(R.p95([r.tpot for r in rec.records
                      if r.finished is not None and rec.t0 < r.finished
                      <= rec.t_end and r.tpot is not None]))
