"""The plain reference against the program's host engine, on small streams
of both configurations' queries: counts at every position and every
complex event."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import reference, spec, streams  # noqa: E402


def host_engine_output(cfg, stream, n):
    """Counts and complex events of the program's host engine."""
    from repro.core import Event, compile_query
    from repro.core.engine import Engine
    from repro.core.partition import PartitionedEngine
    cq = compile_query(cfg["query"])
    q = cq.query

    def make():
        return Engine(cq.cea, window=q.window,
                      consume_on_match=q.consume_on_match)
    eng = PartitionedEngine(make, q.partition_by) if q.partition_by \
        else make()
    counts = np.zeros(n, np.int64)
    ces = {}
    for i in range(n):
        r = stream.raw(i)
        out = eng.process(Event(r["type"], {k: v for k, v in r.items()
                                            if k != "type"}))
        if out:
            counts[i] = len(out)
            ces[i] = {tuple(c.data) for c in out}
    return counts, ces


@pytest.mark.parametrize("config,n,seed", [
    ("stock_q3", 3000, 1), ("stock_q3", 3000, 2 ** 31 + 7),
    ("synth_seq5", 1500, 1), ("synth_seq5", 1500, 2 ** 31 + 7)])
def test_reference_equals_host_engine(config, n, seed):
    cfg = spec.load_config(config)
    s = streams.Stream(cfg["generator"], seed)
    s.grow(n)
    counts, ces = reference.evaluate(cfg["reference"], s.select(np.arange(n)),
                                     s.type_names, n, enumerate_all=True)
    want_counts, want_ces = host_engine_output(cfg, s, n)
    assert (counts > 0).sum() > 10          # the stream has hits to compare
    assert np.array_equal(counts, want_counts)
    assert ces == want_ces


def test_time_window_and_consume_by_hand():
    """SELL;BUY within 10 ms, partitioned, consuming: hand-checked."""
    ref = {"atoms": [{"type": "SELL"}, {"type": "BUY"}],
           "partition_by": "k",
           "window": {"kind": "time", "size": 10.0, "attr": "t"},
           "consume": True}
    #        0  1  2  3  4  5  6
    types = np.array([0, 0, 1, 0, 1, 1, 0])   # 0 SELL, 1 BUY
    cols = {"type": types, "k": np.array([1, 1, 1, 1, 1, 2, 1]),
            "t": np.array([0.0, 5.0, 9.0, 12.0, 30.0, 31.0, 40.0])}
    counts, ces = reference.evaluate(ref, cols, ["SELL", "BUY"], 7,
                                     enumerate_all=True)
    # position 2 closes (0,2) and (1,2); it consumes 0..2; position 4 is
    # 18 ms after 3, outside the window; position 5 has no SELL in key 2
    assert counts.tolist() == [0, 0, 2, 0, 0, 0, 0]
    assert ces == {2: {(0, 2), (1, 2)}}


SELL, BUY, EITHER = "SELL", "BUY", ["BUY", "SELL"]

#: Q2 and Q5 of the paper's Appendix C: comparisons on the price, and in Q5
#: a disjunction of types; the same four names as Q3
PAPER_QUERIES = {
    "Q2": ("SELECT * FROM S WHERE SELL AS msft ; BUY AS oracle ; BUY AS csco"
           " ; SELL AS amat FILTER msft[name = 'MSFT'] AND msft[price > 26.0]"
           " AND oracle[name = 'ORCL'] AND oracle[price > 11.14] AND "
           "csco[name = 'CSCO'] AND amat[name = 'AMAT'] AND "
           "amat[price >= 18.92] WITHIN 30000 [stock_time]",
           [(SELL, "MSFT", [["price", ">", 26.0]]),
            (BUY, "ORCL", [["price", ">", 11.14]]),
            (BUY, "CSCO", []), (SELL, "AMAT", [["price", ">=", 18.92]])]),
    "Q5": ("SELECT * FROM S WHERE SELL AS msft ; (BUY OR SELL) AS oracle ; "
           "(BUY OR SELL) AS csco ; SELL AS amat FILTER msft[name = 'MSFT'] "
           "AND msft[price > 26.0] AND oracle[name = 'ORCL'] AND "
           "oracle[price > 11.14] AND csco[name = 'CSCO'] AND "
           "amat[name = 'AMAT'] AND amat[price >= 18.92] "
           "WITHIN 30000 [stock_time]",
           [(SELL, "MSFT", [["price", ">", 26.0]]),
            (EITHER, "ORCL", [["price", ">", 11.14]]),
            (EITHER, "CSCO", []), (SELL, "AMAT", [["price", ">=", 18.92]])]),
}


@pytest.mark.parametrize("name", sorted(PAPER_QUERIES))
def test_comparisons_and_disjunctions_equal_host_engine(name):
    text, atoms = PAPER_QUERIES[name]
    cfg = dict(spec.load_config("stock_q3"), query=text)
    cfg["reference"] = {
        "atoms": [{"type": t, "eq": {"name": nm}, "where": w}
                  for t, nm, w in atoms],
        "partition_by": None,
        "window": {"kind": "time", "size": 30000.0, "attr": "stock_time"},
        "consume": False}
    n = 320
    s = streams.Stream(cfg["generator"], 2 ** 31 + 3)
    s.grow(n)
    counts, ces = reference.evaluate(cfg["reference"], s.select(np.arange(n)),
                                     s.type_names, n, enumerate_all=True)
    want_counts, want_ces = host_engine_output(cfg, s, n)
    assert (counts > 0).sum() >= 3
    assert np.array_equal(counts, want_counts)
    assert ces == want_ces


def test_an_atom_with_any_is_a_disjunction():
    cols = {"type": np.array([0, 1, 1, 0]), "price": np.array([1., 9., 2., 30.])}
    atom = {"any": [{"type": "BUY"}, {"type": "SELL",
                                      "where": [["price", "<", 5.0]]}]}
    # SELL is 0, BUY is 1: SELL at 1.0, both BUYs, not the SELL at 30.0
    assert reference.atom_mask(atom, cols, ["SELL", "BUY"]).tolist() == \
        [True, True, True, False]


def test_a_type_the_stream_never_draws_matches_nothing():
    """Fig. 8's A1;A2;A3 over a stream without A3: no hit, as in the
    program's host engine."""
    cfg = spec.load_config("synth_seq5")
    cfg = dict(cfg, query="SELECT * FROM S WHERE A1 ; A2 ; A3 WITHIN 3200 "
                          "events",
               generator=dict(cfg["generator"], query_types=["A1", "A2"]))
    cfg["reference"] = dict(cfg["reference"],
                            atoms=[{"type": t} for t in ("A1", "A2", "A3")],
                            window={"kind": "events", "size": 3200})
    n = 600
    s = streams.Stream(cfg["generator"], 4)
    s.grow(n)
    assert "A3" not in s.type_names
    counts, ces = reference.evaluate(cfg["reference"], s.select(np.arange(n)),
                                     s.type_names, n, enumerate_all=True)
    want_counts, _ = host_engine_output(cfg, s, n)
    assert not counts.any() and not want_counts.any() and ces == {}
