"""The admission insert's contract, checked against a poisoned page pool.

Every pool row starts as NaN.  One slot is admitted first and stays live;
then a second slot is admitted from random prefill K/V handed over in
the pool's row layout.  Read back through the block table, each mapped
page must hold the prefill's rows bit for bit (a short last block padded
with zeros), the live slot's pages must be as its own insert left them,
every other pool row must still be NaN, and the insert must report
``ceil(prompt_len / page_size)`` pages written.  Shared by
``test_kv_insert.py`` (one device) and ``dist_scenarios.py`` (four-device
meshes, where pages spread over the pool's shards).
"""
import numpy as np

PAGE, MAX_SEQ, SLOTS, NUM_PAGES = 16, 64, 4, 32
#: the full-length prefill (whole page blocks) and an exact-length one,
#: as a recurrent family's bucket would have it (one block and 4 rows)
S_PRE, S_EXACT = 48, 20
#: case -> (prompt length, prefill length)
CASES = {"one_token": (1, S_PRE), "one_page": (PAGE, S_PRE),
         "page_and_one": (PAGE + 1, S_PRE), "full_prefill": (S_PRE, S_PRE),
         "exact_length": (S_EXACT, S_EXACT)}
#: prompt length of the slot admitted first, which stays live
LIVE_LEN = 2 * PAGE + 1


def _bits(x):
    return np.asarray(x).view(np.uint16 if x.dtype.itemsize == 2
                               else np.uint32)


def _random_prefill(plan_pre, mesh, seed):
    """Random prefill K/V (and state) in the hand-off layout, placed as
    the engine's prefill program returns them; and the same on the
    host."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.specs import prefill_handoff_specs

    structs, specs = prefill_handoff_specs(plan_pre)
    rng = np.random.RandomState(seed)
    host = jax.tree.map(
        lambda s: rng.standard_normal(s.shape).astype(s.dtype), structs)
    dev = jax.tree.map(
        lambda h, sp: jax.device_put(h, NamedSharding(mesh, sp)), host,
        specs, is_leaf=lambda x: isinstance(x, P))
    return dev, host


def _kv(tree):
    return [tree["pos0"]["kv"][k] for k in ("k", "v")]


def check(mesh, case):
    """Run ``case`` of ``CASES`` on ``mesh`` (axes ``data``, ``model``);
    raises AssertionError on a breach of the contract."""
    import jax
    from repro.configs import get_config
    from repro.configs.base import ShapeCell
    from repro.configs.reduced import reduced
    from repro.launch.specs import make_plan
    from repro.serving.kv_cache import PagedKVCache

    P_len, S_pre = CASES[case]
    cfg = reduced(get_config("qwen1.5-0.5b", hnn_mode="ann")).replace(
        codec="none")
    plan = make_plan(cfg, ShapeCell("serve_decode", MAX_SEQ, SLOTS,
                                    "decode"), mesh)
    plan_full = make_plan(cfg, ShapeCell("serve_admit", S_PRE, 1, "prefill"),
                          mesh)
    plan_pre = make_plan(cfg, ShapeCell("serve_admit", S_pre, 1, "prefill"),
                         mesh)
    cache = PagedKVCache(plan, plan_full, mesh, PAGE, NUM_PAGES)
    cache.buffers = jax.tree.map(
        lambda x: jax.device_put(np.full(x.shape, np.nan, x.dtype),
                                 x.sharding), cache.buffers)

    admitted = []
    for seq_len, plan_b, seed in ((LIVE_LEN, plan_full, 1),
                                  (P_len, plan_pre, 2)):
        dev, host = _random_prefill(plan_b, mesh, seed)
        slot, pages = cache.admit(dev, seq_len, plan_pre=plan_b)
        assert pages == -(-seq_len // PAGE), (case, seq_len, pages)
        admitted.append((slot, seq_len, _kv(host)))

    pools = [np.asarray(x) for x in _kv(cache.buffers)]
    untouched = np.ones(NUM_PAGES, bool)
    for slot, seq_len, rows in admitted:
        n = -(-seq_len // PAGE)
        row = cache.block_table[slot]
        assert (row[:n] >= 0).all() and (row[n:] == -1).all(), row
        untouched[row[:n]] = False
        for pool, r in zip(pools, rows):
            r = r[:, 0]                               # [U, S, L]
            pad = n * PAGE - r.shape[1]
            if pad > 0:
                r = np.concatenate(
                    [r, np.zeros((r.shape[0], pad, r.shape[2]), r.dtype)],
                    axis=1)
            for j in range(n):
                got = pool[:, row[j]]
                want = r[:, j * PAGE:(j + 1) * PAGE]
                assert np.array_equal(_bits(got), _bits(want)), (
                    case, slot, j)
    for pool in pools:
        assert np.isnan(pool[:, untouched].astype(np.float32)).all(), case
