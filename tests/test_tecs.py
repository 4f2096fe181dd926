"""tECS structural invariants (paper §5.1–5.2, Theorems 2–3).

Checks that every tECS the engine builds is time-ordered, 3-bounded and that
its construction methods return safe nodes; and that the engine's complexity
guarantees hold empirically (constant update time, linear node growth,
output-linear enumeration delay).
"""
import random
import time

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Event, compile_query
from repro.core.engine import Engine, WindowSpec
from repro.core.tecs import (BOTTOM, OUTPUT, TECS, UNION, Node, new_ulist,
                             ulist_insert, ulist_merge)


def walk_nodes(roots):
    seen, stack = set(), list(roots)
    while stack:
        n = stack.pop()
        if id(n) in seen or n is None:
            continue
        seen.add(id(n))
        yield n
        if n.kind == UNION:
            stack.extend([n.left, n.right])
        elif n.kind == OUTPUT:
            stack.append(n.left)


def engine_roots(engine):
    roots = []
    for ul in engine.T.values():
        roots.extend(ul)
    return roots


def check_invariants(roots):
    for n in walk_nodes(roots):
        if n.kind == UNION:
            # time-ordered: left max-start >= right max-start
            assert n.left.max_start >= n.right.max_start
            assert n.max_start == max(n.left.max_start, n.right.max_start)
            # 3-bounded
            assert n.odepth() <= 3
        elif n.kind == OUTPUT:
            assert n.max_start == n.left.max_start


@pytest.mark.parametrize("qtext", [
    "SELECT * FROM S WHERE A ; B ; C",
    "SELECT * FROM S WHERE A ; B+ ; C",
    "SELECT * FROM S WHERE A ; (B OR C)+ ; A",
])
def test_tecs_invariants_after_every_event(qtext):
    q = compile_query(qtext)
    eng = Engine(q.cea)
    rng = random.Random(7)
    for _ in range(40):
        eng.process(Event(rng.choice("ABCX")))
        check_invariants(engine_roots(eng))
        # union-lists: head is non-union; strictly decreasing max-start after it
        for ul in eng.T.values():
            assert ul[0].kind != UNION
            for a, b in zip(ul[1:], ul[2:]):
                assert a.max_start > b.max_start
            assert all(ul[0].max_start >= n.max_start for n in ul[1:])
            assert all(n.is_safe() for n in ul)


def test_union_requires_equal_max_start():
    t = TECS(check_invariants=True)
    b1, b2 = t.new_bottom(3), t.new_bottom(3)
    u = t.union(b1, b2)
    assert u.max_start == 3 and u.is_safe()
    o = t.extend(u, 7)
    assert o.max_start == 3 and o.pos == 7


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=20), min_size=1, max_size=12))
def test_union_list_insert_properties(starts):
    """insert keeps the union-list sorted and merge preserves the union."""
    t = TECS()
    starts = sorted(starts, reverse=True)
    ul = new_ulist(t.new_bottom(starts[0]))
    for s in starts[1:]:
        ulist_insert(t, ul, t.new_bottom(s))
    assert ul[0].kind == BOTTOM
    for a, b in zip(ul[1:], ul[2:]):
        assert a.max_start > b.max_start
    merged = ulist_merge(t, ul)
    assert merged.max_start == max(starts)
    assert merged.is_safe()
    # the merged node must represent every inserted bottom exactly once per
    # distinct (start) path multiplicity
    leaves = [n.pos for n in walk_nodes([merged]) if n.kind == BOTTOM]
    assert sorted(leaves) == sorted(set(starts)) or sorted(leaves) == sorted(starts)


def test_node_growth_linear_in_stream_length():
    """|tECS| = O(events) — constant nodes per event (paper: constant update)."""
    q = compile_query("SELECT * FROM S WHERE A ; B+ ; C WITHIN 50 events")
    eng = Engine(q.cea, window=WindowSpec.events(50), max_enumerate=10)
    rng = random.Random(3)
    counts = []
    for i in range(2000):
        eng.process(Event(rng.choice("ABCX")))
        if i in (499, 999, 1499, 1999):
            counts.append(eng.tecs.nodes_created)
    # growth between checkpoints should be roughly equal (within 3x)
    deltas = [b - a for a, b in zip(counts, counts[1:])]
    assert max(deltas) < 3 * max(1, min(deltas))


def test_enumeration_delay_linear_in_output_size():
    """Time to enumerate scales with total output size, not partial matches."""
    # A+ over a run of A's: number of matches at j is 2^j capped by enumeration
    q = compile_query("SELECT * FROM S WHERE A ; B WITHIN 400 events")
    eng = Engine(q.cea, window=WindowSpec.events(400))
    for _ in range(400):
        eng.process(Event("A"))
    t0 = time.perf_counter()
    out = eng.process(Event("B"))
    t1 = time.perf_counter()
    assert len(out) == 400
    per_item = (t1 - t0) / len(out)
    # each match is O(1) in size here; delay per item must be tiny and flat
    assert per_item < 2e-4


def test_update_time_independent_of_window():
    """Throughput (updates only) must not degrade with window size (Fig. 8)."""
    def updates_per_sec(window):
        q = compile_query("SELECT * FROM S WHERE A ; B ; C")
        eng = Engine(q.cea, window=WindowSpec.events(window), max_enumerate=0)
        rng = random.Random(0)
        events = [Event(rng.choice(["A", "B", "X1", "X2", "X3"])) for _ in range(1500)]
        t0 = time.perf_counter()
        for e in events:
            eng.process(e)
        return len(events) / (time.perf_counter() - t0)

    small, large = updates_per_sec(50), updates_per_sec(3200)
    assert large > small * 0.4, (small, large)  # flat within noise
