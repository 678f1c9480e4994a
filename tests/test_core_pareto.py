"""Property + unit tests for the ParetoPipe core (the paper's algorithm)."""
import math

import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.core import (Block, BlockGraph, chain, dominates, hypervolume,
                        is_on_front, knee_point, pareto_front)

points = st.lists(
    st.tuples(st.floats(0.01, 100, allow_nan=False),
              st.floats(0.01, 100, allow_nan=False)),
    min_size=1, max_size=60)

# d=3 clouds (latency ↓, throughput ↑, energy ↓); a coarse grid mixed in
# so duplicate coordinates (the staircase's hard case) actually occur
_coord = st.one_of(st.floats(0.01, 100, allow_nan=False),
                   st.integers(1, 5).map(float))
points3 = st.lists(st.tuples(_coord, _coord, _coord),
                   min_size=1, max_size=60)
OBJ3 = ("latency", "throughput", "energy")


@given(points)
@settings(max_examples=200, deadline=None)
def test_front_is_nondominated(pts):
    front = pareto_front(pts)
    for p in front:
        assert not any(dominates(q, p) for q in pts)


@given(points)
@settings(max_examples=200, deadline=None)
def test_every_point_dominated_or_on_front(pts):
    front = set(map(tuple, pareto_front(pts)))
    for p in pts:
        assert tuple(p) in front or any(dominates(q, p) for q in front)


@given(points)
@settings(max_examples=200, deadline=None)
def test_front_monotone(pts):
    """Sorted by latency ascending, throughput must strictly increase."""
    front = pareto_front(pts)
    for a, b in zip(front, front[1:]):
        assert a[0] < b[0] and a[1] < b[1]


@given(points)
@settings(max_examples=100, deadline=None)
def test_front_idempotent(pts):
    f1 = pareto_front(pts)
    assert pareto_front(f1) == f1


@given(points, st.tuples(st.floats(0.01, 100), st.floats(0.01, 100)))
@settings(max_examples=100, deadline=None)
def test_adding_dominated_point_keeps_front(pts, extra):
    front = pareto_front(pts)
    if any(dominates(q, extra) for q in front):
        assert set(map(tuple, pareto_front(pts + [extra]))) \
            == set(map(tuple, front))


@given(points)
@settings(max_examples=100, deadline=None)
def test_hypervolume_nonneg_and_front_invariant(pts):
    ref = max(p[0] for p in pts) * 1.1
    hv_all = hypervolume(pts, ref)
    hv_front = hypervolume(pareto_front(pts), ref)
    assert hv_all >= 0
    assert math.isclose(hv_all, hv_front, rel_tol=1e-9, abs_tol=1e-12)


# ---- d-dimensional properties (the objective-vector protocol) ------------- #
@given(points3)
@settings(max_examples=200, deadline=None)
def test_front3_is_nondominated(pts):
    front = pareto_front(pts, OBJ3)
    for p in front:
        assert not any(dominates(q, p, OBJ3) for q in pts)


@given(points3)
@settings(max_examples=200, deadline=None)
def test_front3_covers_every_point(pts):
    front = set(pareto_front(pts, OBJ3))
    for p in pts:
        assert p in front or any(dominates(q, p, OBJ3) for q in front)


@given(points3)
@settings(max_examples=200, deadline=None)
def test_dominates3_antisymmetric_and_irreflexive(pts):
    for p in pts:
        assert not dominates(p, p, OBJ3)
    for a in pts[:10]:
        for b in pts[:10]:
            assert not (dominates(a, b, OBJ3) and dominates(b, a, OBJ3))


@given(points3)
@settings(max_examples=100, deadline=None)
def test_front3_idempotent(pts):
    f1 = pareto_front(pts, OBJ3)
    assert pareto_front(f1, OBJ3) == f1


@given(points)
@settings(max_examples=200, deadline=None)
def test_d2_path_agrees_with_legacy_sweep(pts):
    """The generalized front must reproduce the original bi-objective
    sort-sweep output exactly (order included)."""
    order = sorted(pts, key=lambda p: (p[0], -p[1]))
    legacy, best_thr = [], float("-inf")
    for p in order:
        if p[1] > best_thr:
            legacy.append(p)
            best_thr = p[1]
    assert pareto_front(pts) == legacy


@given(points3)
@settings(max_examples=100, deadline=None)
def test_hypervolume3_nonneg_front_invariant_and_monotone(pts):
    ref = (max(p[0] for p in pts) * 1.1, min(p[1] for p in pts) * 0.9,
           max(p[2] for p in pts) * 1.1)
    hv_all = hypervolume(pts, ref, OBJ3)
    hv_front = hypervolume(pareto_front(pts, OBJ3), ref, OBJ3)
    assert hv_all >= 0
    assert math.isclose(hv_all, hv_front, rel_tol=1e-9, abs_tol=1e-12)
    # an extra clearly-dominating point can only grow the volume
    better = (0.005, 200.0, 0.005)
    assert hypervolume(pts + [better], ref, OBJ3) >= hv_all - 1e-12


def test_dominates_basic():
    assert dominates((1.0, 10.0), (2.0, 5.0))
    assert not dominates((1.0, 5.0), (2.0, 10.0))
    assert not dominates((1.0, 5.0), (1.0, 5.0))  # equal: no strict improve


def test_knee_on_front():
    pts = [(1, 1), (2, 5), (3, 6), (10, 6.5)]
    k = knee_point(pts)
    assert is_on_front(k, pts)
    assert k in ((2, 5), (3, 6))  # a balanced pick, not an extreme
    assert k != (1, 1) and k != (10, 6.5)


def test_blockgraph_cut_bytes_and_shared_groups():
    blocks = (
        Block("a", 1e6, 100, out_bytes=10),
        Block("b", 1e6, 20, out_bytes=20, shared_group="s", shared_bytes=200),
        Block("c", 1e6, 30, out_bytes=30, shared_group="s", shared_bytes=200),
        Block("d", 1e6, 50, out_bytes=40, broadcast_bytes=7),
        Block("e", 1e6, 60, out_bytes=50),
    )
    g = BlockGraph("t", blocks, input_bytes=5, output_bytes=3)
    assert g.cut_bytes(0) == 5
    assert g.cut_bytes(2) == 20
    assert g.cut_bytes(5) == 3
    assert g.cut_bytes(5 - 1) == 40 + 7  # broadcast edge crosses later cuts...
    # a block's own weights always count; its shared group's once
    # globally and once per segment
    assert g.total_weight_bytes == 100 + 20 + 30 + 200 + 50 + 60
    assert g.segment_weight_bytes(1, 3) == 20 + 30 + 200
    assert g.segment_weight_bytes(2, 4) == 30 + 200 + 50
    assert g.segment_weight_bytes(0, 5) == g.total_weight_bytes
