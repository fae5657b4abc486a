"""The traffic generator and the loops that drive it, on a fake engine and
a fake clock: determinism, clipped lengths, the same work for every seed,
and latencies timed from due times, so a stall counts."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import traffic as T  # noqa: E402

MIX = {"prompt_len": {"median": 128, "sigma": 0.7, "min": 32, "max": 512},
       "output_len": {"median": 64, "sigma": 0.7, "min": 16, "max": 256},
       "requests": 64}
SEED = 2 ** 31 + 977          # seeds run past 32 bits


def test_same_seed_same_requests():
    a = T.make_items(MIX, 1000, SEED, 50, "r")
    b = T.make_items(MIX, 1000, SEED, 50, "r")
    assert [(i.rid, i.prompt, i.max_new) for i in a] == \
        [(i.rid, i.prompt, i.max_new) for i in b]
    assert T.arrival_times(12.0, 20.0, SEED, "r") == \
        T.arrival_times(12.0, 20.0, SEED, "r")
    c = T.make_items(MIX, 1000, SEED + 1, 50, "r")
    assert [i.prompt for i in a] != [i.prompt for i in c]


def test_lengths_clipped_lognormal_in_range():
    n = 2000
    p = T.quantile_lengths(MIX["prompt_len"], n)
    assert p.min() >= 32 and p.max() <= 512
    assert np.median(p) == pytest.approx(128, abs=1)
    # heavy right tail: the top percent reaches the clip, the mean sits
    # above the median
    assert p.max() == 512 and p.mean() > np.median(p)
    for it in T.make_items(MIX, 1000, SEED, 300, "r"):
        assert 32 <= len(it.prompt) <= 512 and 16 <= it.max_new <= 256
        assert all(0 <= t < 1000 for t in it.prompt)


def test_every_seed_offers_the_same_work():
    a = T.make_items(MIX, 1000, 1, 100, "r")
    b = T.make_items(MIX, 1000, 2, 100, "r")
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt)
                                                      for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    ta, tb = T.arrival_times(10.0, 30.0, 1, "r"), T.arrival_times(
        10.0, 30.0, 2, "r")
    assert len(ta) == len(tb) == 300
    assert 0 < min(ta) and max(ta) < 30.0 and ta == sorted(ta)
    # the same gaps in another order: all but one gap are shared
    ga, gb = np.diff([0.0] + ta).round(9), np.diff([0.0] + tb).round(9)
    assert ta != tb and len(set(ga) & set(gb)) >= len(ga) - 1


def test_first_wave_is_staggered():
    items = T.make_items(MIX, 1000, SEED, 64, "r", first_wave=16)
    full = T.make_items(MIX, 1000, SEED, 64, "r")
    fracs = sorted(a.max_new / b.max_new for a, b in zip(items[:16],
                                                         full[:16]))
    assert fracs[0] < 0.2 and fracs[-1] > 0.8
    assert [a.max_new for a in items[16:]] == [b.max_new for b in full[16:]]


def test_item_stream_is_endless_and_unique():
    s = T.item_stream(MIX, 1000, SEED, "r", first_wave=4)
    ids = [next(s).rid for _ in range(3 * MIX["requests"])]
    assert len(set(ids)) == len(ids)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    """Admits everything at once, emits one token per request per step;
    the first step that starts at or after ``stall_t`` takes ``stall_s``
    instead of ``dt``."""

    def __init__(self, clock, dt=0.01, stall_t=None, stall_s=0.0):
        self.clock, self.dt = clock, dt
        self.stall_t, self.stall_s = stall_t, stall_s
        self.observers, self.queue, self.active = [], [], {}
        self.tokens_generated = 0
        self.num_slots = 64

    @property
    def num_active(self):
        return len(self.active)

    @property
    def queue_depth(self):
        return len(self.queue)

    @property
    def idle(self):
        return not self.queue and not self.active

    def pool_stats(self):
        return {"pages_in_use": len(self.active)}

    def _emit(self, ev, *a):
        for o in self.observers:
            getattr(o, ev)(*a)

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        if self.stall_t is not None and self.clock.t >= self.stall_t:
            self.clock.t += self.stall_s
            self.stall_t = None
        else:
            self.clock.t += self.dt
        done = []
        for rid in list(self.active):
            req, toks = self.active[rid]
            toks.append(1)
            self.tokens_generated += 1
            if len(toks) >= req.max_new_tokens:
                del self.active[rid]
                self._emit("on_finish", rid, len(toks))
                done.append((req, toks))
        for req in self.queue:
            self._emit("on_admit", req.rid, 0)
            self._emit("on_first_token", req.rid)
            self.active[req.rid] = (req, [1])
            self.tokens_generated += 1
        self.queue = []
        return done


def _items(n, m=3):
    return [T.Item(f"q{i}", [1, 2, 3], m) for i in range(n)]


def test_a_stall_counts_against_requests_due_during_it():
    clock = Clock()
    eng = FakeEngine(clock, dt=0.01, stall_t=0.5, stall_s=1.0)
    run = T.Run(eng, clock)
    arrivals = [(0.05 * i, it) for i, it in enumerate(_items(60))]
    T.open_loop(run, arrivals, 0.0, 3.0, clock.sleep)
    T.drain_first_tokens(run, list(run.records), 10.0)
    recs = list(run.records.values())
    assert len(recs) == 60 and all(r.first is not None for r in recs)
    # the stall runs from t=0.5 to t=1.5
    hit = [r for r in recs if 0.55 <= r.due <= 1.45]
    calm = [r for r in recs if r.due >= 1.8]
    assert len(hit) >= 15 and calm
    for r in hit:
        # timed from its due time, the request pays for the stall: it
        # waited until the stall's end even though it was submitted later
        assert r.ttft >= 1.5 - r.due - 1e-9
        assert r.submitted - r.due > 0
    assert max(r.ttft for r in calm) < 0.05
    late = T.lateness(hit)
    assert late["max"] > 0.8


def test_closed_loop_sends_the_next_request_when_one_finishes():
    clock = Clock()
    eng = FakeEngine(clock)
    run = T.Run(eng, clock)
    items = iter(_items(1000, m=4))
    for _ in range(8):
        run.submit(next(items), clock())
    T.closed_loop(run, items, 1.0)
    finished = [r for r in run.records.values() if r.finished is not None]
    assert finished and eng.num_active + eng.queue_depth == 8
    for r in finished:
        assert len(r.tokens) == 4 and r.tpot == pytest.approx(0.01)
    steps = run.steps
    assert steps and all(s[1] <= 8 for s in steps)
