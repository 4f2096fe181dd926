"""The plain reference: sequence patterns over a stream, counted and listed.

It imports nothing of the program.  A configuration states its query twice:
as CEQL text for the program, and as ``reference`` data for this module::

    {"atoms": [{"type": "SELL", "eq": {"name": "MSFT"},
                "where": [["price", ">", 26.0]]},
               {"type": ["BUY", "SELL"], "eq": {"name": "ORCL"}},
               {"any": [{"type": "BUY"}, {"type": "SELL",
                                          "where": [["price", "<", 9.0]]}]},
               ...],
     "partition_by": "volume",                       # or null
     "window": {"kind": "time", "size": 30000.0, "attr": "stock_time"},
     "consume": true}

An atom is one event: of one of its types (``(BUY OR SELL)``), with each
``eq`` attribute equal to its value and each ``where`` comparison true; an
atom with ``any`` is the disjunction of the atoms it lists.  A type the
stream never draws matches nothing.  Kleene closure is not expressed.

Semantics (CORE §3, skip-till-any-match, ``SELECT *``): a complex event
closing at position ``j`` is a chain ``a_0 < a_1 < ... < a_{m-2} < j`` of
events of one partition, ``a_k`` satisfying atom ``k`` and ``j`` the last
atom.  Count windows admit ``a_0 >= j - size`` in substream positions; time
windows admit ``ts(a_0) >= ts(j) - size``.  ``CONSUME BY ANY``: once a
position closes a complex event, no event of its partition up to it joins a
later one.
"""
from __future__ import annotations

from bisect import bisect_right
from typing import Dict, List, Optional, Set, Tuple

import numpy as np


_OPS = {"=": np.equal, "!=": np.not_equal, "<": np.less,
        "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal}


def atom_mask(atom: dict, cols: Dict[str, np.ndarray],
              type_names: List[str]) -> np.ndarray:
    """Booleans over the events: event ``i`` satisfies ``atom``."""
    if "any" in atom:
        m = np.zeros(len(cols["type"]), bool)
        for alt in atom["any"]:
            m |= atom_mask(alt, cols, type_names)
        return m
    types = atom["type"]
    types = [types] if isinstance(types, str) else list(types)
    m = np.isin(cols["type"], [type_names.index(t) for t in types
                               if t in type_names])
    for attr, value in atom.get("eq", {}).items():
        m &= cols[attr] == value
    for attr, op, value in atom.get("where", []):
        m &= _OPS[op](cols[attr], value)
    return m


def atom_masks(ref: dict, cols: Dict[str, np.ndarray],
               type_names: List[str]) -> np.ndarray:
    """``(m, n)`` booleans: event ``i`` satisfies atom ``k``."""
    return np.stack([atom_mask(a, cols, type_names) for a in ref["atoms"]])


def partitions(ref: dict, cols: Dict[str, np.ndarray], n: int
               ) -> List[np.ndarray]:
    """Global positions of each substream, in stream order."""
    key = ref.get("partition_by")
    if not key:
        return [np.arange(n)]
    vals = cols[key]
    return [np.nonzero(vals == v)[0] for v in np.unique(vals)]


def earliest_start(ref: dict, cols: Dict[str, np.ndarray],
                   idx: np.ndarray) -> np.ndarray:
    """Per substream position ``j``: the first admissible start."""
    w = ref["window"]
    local = np.arange(len(idx))
    if w["kind"] == "events":
        return np.maximum(0, local - int(w["size"]))
    ts = np.asarray(cols[w["attr"]], np.float64)[idx]
    return np.searchsorted(ts, ts - float(w["size"]), side="left")


def _chains(lists: List[List[int]], end: int) -> Set[Tuple[int, ...]]:
    """Every increasing chain taking one position from each list."""
    out: Set[Tuple[int, ...]] = set()

    def walk(k: int, after: int, prefix: Tuple[int, ...]) -> None:
        if k == len(lists):
            out.add(prefix + (end,))
            return
        pos = lists[k]
        for p in pos[bisect_right(pos, after):]:
            walk(k + 1, p, prefix + (p,))
    walk(0, -1, ())
    return out


def evaluate(ref: dict, cols: Dict[str, np.ndarray], type_names: List[str],
             n: int, dtype=np.int64, enumerate_all: bool = False
             ) -> Tuple[np.ndarray, Optional[Dict[int, Set[Tuple[int, ...]]]]]:
    """Counts of complex events closing at each of the ``n`` positions, and
    with ``enumerate_all`` every complex event of every hit, as tuples of
    global positions keyed by the closing position.

    ``dtype`` is the type the chains are counted in: ``int64`` is exact;
    a narrower float shows what a lower precision would report.
    """
    masks = atom_masks(ref, cols, type_names)
    last = masks.shape[0] - 1
    counts = np.zeros(n, np.int64)
    ces: Optional[Dict[int, Set[Tuple[int, ...]]]] = \
        {} if enumerate_all else None
    for idx in partitions(ref, cols, n):
        m = masks[:, idx]
        lo = earliest_start(ref, cols, idx)
        consumed = -1                       # last substream position used up
        for j in np.nonzero(m[last])[0]:
            start = max(int(lo[j]), consumed + 1)
            if start >= j:
                continue
            f = m[0, start:j].astype(dtype)
            for k in range(1, last):
                before = np.cumsum(f, dtype=dtype) - f     # chains ending < p
                f = before * m[k, start:j].astype(dtype)
            c = int(np.sum(f, dtype=dtype))
            if c <= 0:
                continue
            counts[idx[j]] = c
            if ces is not None:
                lists = [sorted(idx[start + np.nonzero(m[k, start:j])[0]]
                                .tolist()) for k in range(last)]
                ces[int(idx[j])] = _chains(lists, int(idx[j]))
            if ref.get("consume"):
                consumed = int(j)
    return counts, ces
