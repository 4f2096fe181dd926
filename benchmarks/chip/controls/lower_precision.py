"""The reference one precision below what the configuration states: the
columns named in ``columns`` (timestamps, prices) rounded to ``dtype``, and
the chains counted in ``dtype``."""
import ml_dtypes
import numpy as np

from chipbench import reference


def outputs(cfg, cols, type_names, n):
    ctl = cfg["control"]
    dtype = getattr(ml_dtypes, ctl["dtype"])
    cols = dict(cols)
    for name in ctl.get("columns", []):
        cols[name] = np.asarray(cols[name]).astype(dtype).astype(np.float64)
    return reference.evaluate(cfg["reference"], cols, type_names, n,
                              dtype=dtype,
                              enumerate_all=bool(cfg["sink"]["enumerate"]))
