"""The reference listing at most ``n`` complex events per hit (the paper's
"first ten results" of Fig. 7), where the configuration promises every
one."""
from chipbench import reference


def outputs(cfg, cols, type_names, n):
    counts, ces = reference.evaluate(cfg["reference"], cols, type_names, n,
                                     enumerate_all=True)
    cap = int(cfg["control"]["n"])
    return counts, {p: set(sorted(s)[:cap]) for p, s in ces.items()}
