"""Engine ⇔ brute-force-oracle equivalence (paper Table 2 semantics, Thm 3)."""
import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Event, compile_query
from repro.core.cel import complex_events as oracle_ce


def run_engine(qtext, stream, **kw):
    q = compile_query(qtext)
    return sorted((ce.start, ce.end, ce.data) for _, ce in q.run(stream, **kw))


def run_oracle(qtext, stream, epsilon=None):
    q = compile_query(qtext)
    return sorted(oracle_ce(q.query.formula(), stream, epsilon=epsilon))


def rand_stream(seed, n, alphabet=("A", "B", "C", "X"), with_attrs=False):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        attrs = {"v": rng.randint(0, 9)} if with_attrs else {}
        out.append(Event(rng.choice(alphabet), attrs))
    return out


QUERIES = [
    ("SELECT * FROM S WHERE A AS x ; B AS y", None, False),
    ("SELECT * FROM S WHERE A ; B ; C", None, False),
    ("SELECT * FROM S WHERE A ; (B OR C) ; A", None, False),
    ("SELECT * FROM S WHERE A ; B+ ; C", None, False),
    ("SELECT * FROM S WHERE (A ; B)+", None, False),
    ("SELECT * FROM S WHERE (A OR B)+ ; C", None, False),
    ("SELECT * FROM S WHERE A ; B WITHIN 4 events", 4, False),
    ("SELECT * FROM S WHERE A ; B+ ; C WITHIN 5 events", 5, False),
    ("SELECT x FROM S WHERE A AS x ; B AS y", None, False),
    ("SELECT y FROM S WHERE A AS x ; (B OR C) AS y", None, False),
    ("SELECT * FROM S WHERE A AS x ; B AS y FILTER x[v > 5] AND y[v <= 3]",
     None, True),
    ("SELECT * FROM S WHERE A AS x ; B AS y FILTER x[v > 8] OR x[v < 1]",
     None, True),
    ("SELECT * FROM S WHERE A AS x FILTER x[v >= 2 AND v <= 7]", None, True),
]


@pytest.mark.parametrize("qtext,eps,attrs", QUERIES)
@pytest.mark.parametrize("seed", range(5))
def test_engine_matches_oracle(qtext, eps, attrs, seed):
    n = 10 if "+" in qtext else 14
    stream = rand_stream(seed, n, with_attrs=attrs)
    assert run_engine(qtext, stream) == run_oracle(qtext, stream, epsilon=eps)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.sampled_from("ABCX"), min_size=1, max_size=9),
       st.sampled_from([q for q, _, a in QUERIES if not a and "WITHIN" not in q]))
def test_engine_matches_oracle_hypothesis(types, qtext):
    stream = [Event(t) for t in types]
    assert run_engine(qtext, stream) == run_oracle(qtext, stream)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.sampled_from("ABX"), min_size=1, max_size=10),
       st.integers(min_value=1, max_value=6))
def test_window_semantics_hypothesis(types, eps):
    """WITHIN ε keeps exactly the complex events with end-start ≤ ε."""
    stream = [Event(t) for t in types]
    qtext = f"SELECT * FROM S WHERE A ; B WITHIN {eps} events"
    assert run_engine(qtext, stream) == run_oracle(qtext, stream, epsilon=eps)
    # windowed output ⊆ unwindowed output, and every dropped match violates ε
    unwindowed = run_oracle("SELECT * FROM S WHERE A ; B", stream)
    windowed = set(run_engine(qtext, stream))
    assert windowed <= set(unwindowed)
    for (i, j, d) in set(unwindowed) - windowed:
        assert j - i > eps


def test_incremental_emission_positions():
    """Matches are emitted at the position where their last event arrives."""
    q = compile_query("SELECT * FROM S WHERE A ; B")
    ex = q.make_executor()
    seen = []
    for t in [Event(x) for x in "ABAB"]:
        for ce in ex.process(t):
            seen.append((ex.j, ce.end))
    assert all(j == end for j, end in seen)
    assert len(seen) == 3  # (0,1), (0,3), (2,3)


def test_time_window_attribute():
    """WITHIN 30000 [ts] uses the named attribute as the clock (stock queries)."""
    qtext = "SELECT * FROM S WHERE A AS x ; B AS y WITHIN 10 [ts]"
    stream = [Event("A", {"ts": 0}), Event("B", {"ts": 5}),
              Event("A", {"ts": 100}), Event("B", {"ts": 105}),
              Event("B", {"ts": 111})]
    got = run_engine(qtext, stream)
    # (0,1) Δts=5 ok; (2,3) Δts=5 ok; (0,3)/(0,4)/(2,4) Δts>10 dropped
    assert got == [(0, 1, (0, 1)), (2, 3, (2, 3))]


def test_consume_on_match():
    """CONSUME BY ANY forgets all partial matches once a match fires."""
    qtext = "SELECT * FROM S WHERE A ; B CONSUME BY ANY"
    stream = [Event(t) for t in "AABB"]
    got = run_engine(qtext, stream)
    # at j=2 both (0,2) and (1,2) fire, then state resets -> j=3 yields nothing
    assert got == [(0, 2, (0, 2)), (1, 2, (1, 2))]


def test_partition_by_two_keys():
    q = compile_query(
        "SELECT * FROM S WHERE S1 AS a ; S2 AS b PARTITION BY [k], [w]")
    stream = [Event("S1", {"k": 1, "w": 1}), Event("S1", {"k": 1, "w": 2}),
              Event("S2", {"k": 1, "w": 1}), Event("S2", {"k": 1, "w": 2}),
              Event("S2", {"k": 2, "w": 1})]
    got = sorted((ce.start, ce.end, ce.data) for _, ce in q.run(stream))
    assert got == [(0, 2, (0, 2)), (1, 3, (1, 3))]


def test_partition_null_attribute_excluded():
    q = compile_query("SELECT * FROM S WHERE A ; B PARTITION BY [k]")
    stream = [Event("A", {"k": 1}), Event("B", {}), Event("B", {"k": 1})]
    got = sorted((ce.start, ce.end, ce.data) for _, ce in q.run(stream))
    assert got == [(0, 2, (0, 2))]  # NULL-k event joins no substream


def test_max_enumerate_cap():
    """The experiments enumerate only the first 10 results per position."""
    q = compile_query("SELECT * FROM S WHERE A ; B")
    stream = [Event("A") for _ in range(30)] + [Event("B")]
    got = list(q.run(stream, max_enumerate=10))
    assert len(got) == 10
