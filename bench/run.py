"""Run one cell of the on-chip serving benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json`` and one reader per metric
in ``bench/metrics/<metric>.py``.

A run builds the serving engine from the configuration with weights made
from the seed, warms up the cell's shapes and brings the batch to a
steady mix (all of that is ``setup_s``), then drives ``submit`` / ``step``
for ``--seconds`` with the cell's traffic.  With ``--trace 1`` a few
seconds inside the window are traced with the profiler, and the result
carries the per-layer metrics instead of the end-to-end ones.  After the
window the engine's pipeline is drained, layer 0 of the KV pool is read
for a sample of the live slots, the engine is freed, and those rows and a
sample of the served requests are checked against the plain reference
(``check.py``); every number compared is printed beside its limit, last
on standard error and last in the result.

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: fixed in-checkout directory the traced run writes its profile to
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: after the window: how long to wait for requests still owed a token
WAIT_S = 60.0


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def cell_of(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def metrics_for(manifest: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_accelerator(chips: int) -> list:
    """The cell's TPU devices; exits non-zero on anything else."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: no TPU (JAX found {devs[0].platform}); "
                         "this benchmark never falls back to another device")
    if len(devs) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


class _Compiles:
    """Counts backend compilations while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0

    def __call__(self, event, secs, **kw):
        if self.on and event == COMPILE_EVENT:
            self.n += 1


def _wrap_spans(engine, span):
    """Host spans around the engine's own dispatch and commit (instance
    attributes shadow the methods that ``step`` calls)."""
    for name in ("dispatch", "commit"):
        fn = getattr(engine, name)

        def wrapped(*a, _fn=fn, _n=f"bench.{name}"):
            with span(_n):
                return _fn(*a)
        setattr(engine, name, wrapped)


def _unwrap_spans(engine):
    for name in ("dispatch", "commit"):
        engine.__dict__.pop(name, None)


def run_cell(manifest: dict, cell: dict, config: dict, traffic: dict,
             seed: int, seconds: float, trace: bool, t_start: float,
             modes=None):
    """One run; returns (result dict, checks dict).  ``modes`` (a tuple,
    maybe empty) puts every reading of the reference in the result, with
    those of the reference's control or witness modes it names
    (``control.py``; a benchmark run reads none)."""
    import jax
    import numpy as np
    from repro.launch.compile_cache import enable_compile_cache

    from bench import check, reduce_trace, serve, work
    from bench import traffic as T

    chips = int(cell["chips"])
    devs = require_accelerator(chips)
    dev = devs[0]
    # the peaks of a TPU; nothing on any other device, so a rehearsal off
    # the chip can report no share of a peak
    peak = work.peak_for(dev.device_kind) if dev.platform == "tpu" else None
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; compile "
          f"cache: {enable_compile_cache()}", file=sys.stderr, flush=True)
    # cache every program, the small ones too, so that set-up after a
    # checkout's first run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    eng = traffic["engine"]
    vocab = config["vocab_size"]
    engine, params = serve.build(config, eng, seed)
    engine.warmup(T.make_items(traffic, vocab, seed, 1, "w")[0].prompt)
    clock = time.perf_counter
    run = T.Run(engine, clock, jax.profiler.TraceAnnotation)

    if traffic["loop"] == "closed":
        clients = traffic["clients"]
        items = T.item_stream(traffic, vocab, seed, "r", first_wave=clients)
        for _ in range(clients):
            run.submit(next(items), clock())
        while engine.queue_depth:
            for _ in run.step():
                run.submit(next(items), clock())

        def go(until):
            T.closed_loop(run, items, until)
    else:
        rate, warm_s = traffic["rate_per_s"], traffic["warmup_s"]
        w_at = T.arrival_times(rate, warm_s, seed, "u")
        w_items = T.make_items(traffic, vocab, seed, len(w_at), "u")
        t_w = clock()
        T.open_loop(run, list(zip(w_at, w_items)), t_w, t_w + warm_s,
                    time.sleep)
        at = T.arrival_times(rate, seconds, seed, "r")
        arrivals = list(zip(at, T.make_items(traffic, vocab, seed, len(at),
                                             "r")))
        state = {"next": 0}

        def go(until):
            state["next"] = T.open_loop(run, arrivals, t0, until, time.sleep,
                                        start=state["next"])

    jax.block_until_ready(engine.cache.buffers)
    t0 = clock()
    tokens0 = engine.tokens_generated
    setup_s = t0 - t_start
    print(f"setup: {setup_s:.2f} s", file=sys.stderr, flush=True)
    compiles.on = True
    traced_steps = None
    if trace:
        lead, span_s = min(1.0, seconds / 4), min(3.0, seconds / 2)
        go(t0 + lead)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        i0 = len(run.steps)
        _wrap_spans(engine, jax.profiler.TraceAnnotation)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        go(t0 + lead + span_s)
        jax.profiler.stop_trace()
        _unwrap_spans(engine)
        traced_steps = (i0, len(run.steps))
    go(t0 + seconds)
    t_end = t0 + seconds
    compiles.on = False
    records = list(run.records.values())
    due = [r for r in records if t0 <= r.due < t_end]
    waiting = sum(1 for r in due if r.first is None)
    T.drain_first_tokens(run, [r.item.rid for r in due], t_end + WAIT_S)
    t_wait = clock()
    unanswered = sum(1 for r in due if r.first is None)
    finished = sum(1 for r in records
                   if r.finished is not None and t0 <= r.finished < t_end)
    print(f"window: {len(run.steps)} steps, {len(due)} requests due, "
          f"{finished} finished, {waiting} without a first token at the "
          f"close, {compiles.n} "
          f"compiles inside; generator lateness (s) {T.lateness(due)}",
          file=sys.stderr, flush=True)
    jax.monitoring.unregister_event_duration_listener(compiles)

    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
    wire = engine.wire_stream_profile() if trace else None
    bad = check.bad_outputs(records, vocab)
    picked = check.sample(records, seed, traffic["check"]["requests"])
    pool = check.pool_rows(engine, seed, traffic["check"]["slots"])
    engine.observers.clear()
    run.engine = None
    del engine, params
    gc.collect()

    t_ref = clock()
    readings = check.read_reference(config, eng["max_seq"], seed, picked,
                                    pool, modes or ())
    print(f"reference: {readings} in {clock() - t_ref:.1f} s",
          file=sys.stderr, flush=True)

    tr = reduce_trace.load(TRACE_DIR) if trace else None
    rec = types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, dims=work.Dims.of(config),
        peak=peak, chips=chips, slots=eng["num_slots"],
        page_size=eng["page_size"], t0=t0, t_end=t_end, t_wait=t_wait,
        tokens0=tokens0, setup_s=setup_s, steps=run.steps, records=records,
        due_in_window=due, trace=tr, traced_steps=traced_steps, wire=wire)
    metrics = {}
    for m in metrics_for(manifest, cell["name"], trace):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    checks = check.verdict(readings, traffic["check"]["limits"])
    checks.update({
        "bad_outputs": {"value": bad, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "unchecked_sample": {"value": int(not picked or not pool),
                             "limit": 0},
    })
    correct = check.passed(checks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    result = {"correct": correct, "attempted": len(due),
              "failed": unanswered, "metrics": metrics, "device": device}
    if tr is not None and tr.devices:
        lo, hi = tr.window
        device["busy_s"] = float(np.mean(
            [reduce_trace.busy_seconds(d, lo, hi) for d in tr.devices]))
        device["window_s"] = hi - lo
        result["breakdown"] = reduce_trace.breakdown(tr)
    if modes is not None:
        result["readings"] = readings
    result["checks"] = checks
    return result, checks


def main(argv=None):
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = load_json(ROOT / "BENCHMARK.json")
    cell = cell_of(manifest, args.workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    result, checks = run_cell(manifest, cell, config, traffic, args.seed,
                              args.seconds, bool(args.trace), t_start)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    main()
