"""The control: the plain reference in the program's place, one guarantee
broken, scored by the same comparison a run is.

A configuration's ``control`` names its kind, a file
``controls/<kind>.py`` whose ``outputs(cfg, cols, type_names, n)`` gives
what the control reports: counts per position and the complex events of
each hit.  ``lower_precision`` computes in the next precision below the one
the configuration states; ``enumerate_at_most`` lists at most ``n`` complex
events per hit where the configuration promises every one.  The control
has to come out not correct; the readings it gives are the upper ends the
limits were set against.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from . import reference, spec, streams
from .drive import compare


def outputs(cfg: dict, cols, type_names: List[str], n: int,
            root: str = spec.CHIP_DIR):
    """What the configuration's control reports for these events."""
    return spec.load_control(cfg["control"]["kind"], root).outputs(
        cfg, cols, type_names, n)


def as_service_output(counts: np.ndarray, ces, L: int
                      ) -> Tuple[List[dict], Dict[int, float], Dict[int, set]]:
    """Counts and lists in the form a run collects them: match-log
    records per chunk, delivered alerts, enumerated complex events."""
    records = []
    for c in range(len(counts) // L):
        part = counts[c * L:(c + 1) * L]
        nz = np.nonzero(part)[0]
        records.append({"chunk": c,
                        "counts": [[[int(i)], int(part[i])] for i in nz],
                        "hits": [int(c * L + i) for i in nz]})
    delivered = {int(p): 0.0 for p in np.nonzero(counts)[0]}
    return records, delivered, (dict(ces) if ces is not None else {})


def readings(cfg: dict, seed: int, n: int, control: bool,
             root: str = spec.CHIP_DIR) -> Dict[str, Tuple[int, int]]:
    """The numbers a run compares, for ``n`` events of ``seed``, with the
    reference (``control=False``: a sound run) or the control in the
    program's place."""
    L = int(cfg["engine"]["chunk_len"])
    n = n // L * L
    s = streams.Stream(cfg["generator"], seed, root)
    s.grow(n)
    cols = s.select(np.arange(n))
    enum = bool(cfg["sink"]["enumerate"])
    want, want_ces = reference.evaluate(cfg["reference"], cols, s.type_names,
                                        n, enumerate_all=enum)
    if control:
        got, got_ces = outputs(cfg, cols, s.type_names, n, root)
    else:
        got, got_ces = want, want_ces
    records, delivered, enumerated = as_service_output(got, got_ces, L)
    return compare(want, want_ces, records, delivered, enumerated,
                   n_processed=n, n_accepted=n, L=L, guards={})
