"""parse_collectives on synthetic HLO: group sizing from replica_groups
(explicit + iota + num_partitions fallback), the unsized-group warning
that replaced the silent ``default_group=2`` guess, semantic stream
classification from ``jax.named_scope`` op_name trails, and coded-wire
detection.

Pure text parsing — no jax, no jit — so the whole file is tier-1 fast.
The compiled-HLO end-to-end counterpart (a real (2,4) mesh dry-run)
lives in tests/dist_scenarios.py::scenario_mini_dryrun.
"""
import warnings

import pytest

from repro.launch.roofline import CollectiveOp, parse_collectives

HEADER = "HloModule jit_step, num_partitions=8\n"


def _op(body):
    return HEADER + f"  {body}\n"


# ---------------------------------------------------------------------------
# group sizing
# ---------------------------------------------------------------------------


def test_explicit_replica_groups_sizes_the_ring():
    """Explicit {{...}} groups: a tp=4 all-gather prices (n-1)/n = 3/4,
    regardless of any default_group the caller passes."""
    line = ('x = f32[16]{0} all-gather(f32[4]{0} p), '
            'replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}')
    for dg in (None, 2, 16):
        st = parse_collectives(_op(line), default_group=dg)
        assert st.counts == {"all-gather": 1}
        (op,) = st.ops
        assert op.group == 4
        assert st.wire_bytes == pytest.approx(16 * 4 * 3 / 4)


def test_iota_replica_groups():
    """Iota form [num_groups,group_size]<=[N]: the SECOND number is the
    participant count."""
    line = ('x = f32[8]{0} reduce-scatter(f32[32]{0} p), '
            'replica_groups=[2,4]<=[8], dimensions={0}')
    st = parse_collectives(_op(line))
    (op,) = st.ops
    assert op.group == 4
    # reduce-scatter result f32[8] is the 32-byte shard: (n-1) * T
    assert st.wire_bytes == pytest.approx(32 * 3)


def test_empty_groups_fall_back_to_num_partitions():
    """XLA prints the all-device group as ``{}``; the module header's
    num_partitions then sizes the ring — NOT the old default of 2."""
    line = ('x = f32[8]{0} all-reduce(f32[8]{0} p), replica_groups={}, '
            'to_apply=add')
    st = parse_collectives(_op(line))
    (op,) = st.ops
    assert op.group == 8
    assert st.wire_bytes == pytest.approx(2 * 8 * 4 * 7 / 8)


def test_unsized_group_warns_and_uses_default():
    """Bug regression: no replica_groups and no num_partitions header
    used to silently assume n=2; it still falls back (so old artifacts
    parse) but now says so."""
    text = ('HloModule jit_step\n'
            '  x = f32[8]{0} all-reduce(f32[8]{0} p), to_apply=add\n')
    with pytest.warns(RuntimeWarning, match="no\n?.*replica_groups"):
        st = parse_collectives(text)
    assert st.ops[0].group == 2
    with pytest.warns(RuntimeWarning, match="group size 4"):
        st4 = parse_collectives(text, default_group=4)
    assert st4.ops[0].group == 4
    # sized ops never warn
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        parse_collectives(_op(
            'x = f32[8]{0} all-reduce(f32[8]{0} p), replica_groups={}, '
            'to_apply=add'))


def test_permute_is_group_free():
    """collective-permute bytes are point-to-point: T, no ring factor,
    and no warning even without replica_groups."""
    line = ('x = f32[64]{0} collective-permute(f32[64]{0} p), '
            'source_target_pairs={{0,4},{4,0}}')
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        st = parse_collectives(_op(line))
    assert st.wire_bytes == pytest.approx(64 * 4)


def test_singleton_group_moves_no_bytes():
    line = ('x = f32[8]{0} all-gather(f32[8]{0} p), '
            'replica_groups={{0}}, dimensions={0}')
    st = parse_collectives(_op(line))
    assert st.wire_bytes == 0.0 and st.ops == []


# ---------------------------------------------------------------------------
# semantic streams + coded detection
# ---------------------------------------------------------------------------


def test_stream_classification_from_named_scopes():
    """op_name scope trails (repro.core.boundary's jax.named_scope) map
    collectives onto semantic streams; unlabeled ops fall back to their
    HLO kind."""
    text = HEADER + "\n".join([
        '  a = u8[8]{0} all-gather(u8[2]{0} p), replica_groups=[2,4]<=[8],'
        ' dimensions={0}, metadata={op_name="jit(step)/'
        'coded_head_all_gather/all_gather"}',
        '  b = s8[8]{0} all-gather(s8[2]{0} q), replica_groups=[2,4]<=[8],'
        ' dimensions={0}, metadata={op_name="jit(step)/'
        'coded_combine_partials/all_gather"}',
        '  c = u8[16]{0} collective-permute(u8[16]{0} r), '
        'source_target_pairs={{0,1}}, metadata={op_name="jit(step)/'
        'coded_kv_migrate/ppermute"}',
        '  d = f32[8]{0} all-reduce(f32[8]{0} s), replica_groups={}, '
        'to_apply=add, metadata={op_name="jit(step)/transformer/psum"}',
    ]) + "\n"
    st = parse_collectives(text)
    streams = {op.stream for op in st.ops}
    assert streams == {"head_all_gather", "partial_combine",
                       "kv_migrate", "psum"}
    assert set(st.by_stream) == streams
    assert sum(st.by_stream.values()) == pytest.approx(st.wire_bytes)
    by = {op.stream: op for op in st.ops}
    assert by["head_all_gather"].coded
    assert by["partial_combine"].coded
    assert by["kv_migrate"].coded
    assert not by["psum"].coded
    assert by["kv_migrate"].kind == "collective-permute"


def test_kind_fallback_streams():
    text = HEADER + "\n".join([
        '  a = f32[8]{0} all-gather(f32[2]{0} p), '
        'replica_groups=[2,4]<=[8], dimensions={0}',
        '  b = f32[8]{0} reduce-scatter(f32[32]{0} q), '
        'replica_groups=[2,4]<=[8], dimensions={0}',
    ]) + "\n"
    st = parse_collectives(text)
    assert [op.stream for op in st.ops] == ["all_gather", "psum"]


def test_tuple_result_and_coded_mix():
    """Tuple-shaped results sum every leaf; a mixed fp/int tuple is NOT
    a coded boundary."""
    line = ('x = (f32[4]{0}, s8[4]{0}) all-to-all(f32[4]{0} p, s8[4]{0} q)'
            ', replica_groups=[2,4]<=[8], dimensions={0}')
    st = parse_collectives(_op(line))
    (op,) = st.ops
    assert op.t_bytes == pytest.approx(4 * 4 + 4)
    assert not op.coded
    assert op.stream == "all_to_all"
    assert st.wire_bytes == pytest.approx((16 + 4) * 3 / 4)


def test_collective_op_is_frozen_record():
    op = CollectiveOp("all-gather", "psum", 2, 8.0, 4.0, False)
    with pytest.raises(Exception):
        op.bytes = 1.0


# ---------------------------------------------------------------------------
# collectives inside loops
# ---------------------------------------------------------------------------

#: a layer scan whose body gathers once directly and once inside a fusion,
#: a second loop whose trip count only its condition states, and a gather
#: outside both; the helper computations are printed before their callers
LOOPS = HEADER + """
%gather_fusion (p0: s8[2]) -> s8[8] {
  %p0 = s8[2]{0} parameter(0)
  ROOT %g = s8[8]{0} all-gather(s8[2]{0} %p0), replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(step)/while/body/spike_exchange/all_gather"}
}

%body (b: (s32[], s8[2])) -> (s32[], s8[2]) {
  %b = (s32[], s8[2]{0}) parameter(0)
  %x = s8[2]{0} get-tuple-element(%b), index=1
  %a = s8[8]{0} all-gather(s8[2]{0} %x), replica_groups={{0,1,2,3}}, dimensions={0}
  %f = s8[8]{0} fusion(s8[2]{0} %x), kind=kOutput, calls=%gather_fusion
  ROOT %t = (s32[], s8[2]{0}) tuple(%b)
}

%cond (c: (s32[], s8[2])) -> pred[] {
  %c = (s32[], s8[2]{0}) parameter(0)
  %i = s32[] get-tuple-element(%c), index=0
  %n = s32[]{:T(128)} constant(40)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

%body2 (b2: (s32[], f32[4])) -> (s32[], f32[4]) {
  %b2 = (s32[], f32[4]{0}) parameter(0)
  %y = f32[4]{0} get-tuple-element(%b2), index=1
  %r = f32[4]{0} all-reduce(f32[4]{0} %y), replica_groups={{0,1}}, to_apply=%add
  ROOT %t2 = (s32[], f32[4]{0}) tuple(%b2)
}

%cond2 (c2: (s32[], f32[4])) -> pred[] {
  %c2 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt2 = pred[] compare(s32[] %c2, s32[] %c2), direction=LT
}

ENTRY %main (p: s8[2], q: f32[4]) -> s8[8] {
  %p = s8[2]{0} parameter(0)
  %w = (s32[], s8[2]{0}) while(%p), condition=%cond, body=%body
  %w2 = (s32[], f32[4]{0}) while(%q), condition=%cond2, body=%body2, backend_config={"known_trip_count":{"n":"3"}}
  ROOT %o = s8[8]{0} all-gather(s8[2]{0} %p), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""


def test_a_collective_in_a_loop_counts_once_per_iteration():
    st = parse_collectives(LOOPS)
    assert [op.count for op in st.ops] == [40, 40, 3, 1]
    assert st.counts == {"all-gather": 81, "all-reduce": 3}
    # gather: 8 B result, 3/4 of it received; all-reduce: 2 * 16 B * 1/2
    assert st.by_kind == {"all-gather": pytest.approx(81 * 6.0),
                          "all-reduce": pytest.approx(3 * 16.0)}
    assert st.wire_bytes == pytest.approx(81 * 6.0 + 3 * 16.0)
    assert sum(st.by_stream.values()) == pytest.approx(st.wire_bytes)
    # without ENTRY nothing is known of the calls: each op counts once
    st1 = parse_collectives(LOOPS.replace("ENTRY ", ""))
    assert [op.count for op in st1.ops] == [1, 1, 1, 1]
