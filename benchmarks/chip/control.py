#!/usr/bin/env python3
"""Score a cell's control at the cell's own size: it has to fail.

    python3 benchmarks/chip/control.py --workload <cell> --events <n> \
        --seeds <s1> <s2> <s3> ...

The control is the plain reference with one of the configuration's
guarantees broken (``chipbench/control.py``); ``--events`` is the number of
events a run of the cell processes.  Prints each compared number beside its
limit, for the reference itself (every number 0) and for the control, and
exits non-zero if any seed's control comes out correct.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--events", type=int, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path.insert(0, HERE)
    from chipbench import control, spec
    cell = spec.find_cell(spec.load_benchmark(), args.workload)
    cfg = spec.load_config(cell["config"])
    passed = 0
    for seed in args.seeds:
        for label, ctl in (("reference", False), ("control", True)):
            checks = control.readings(cfg, seed, args.events, ctl)
            ok = all(v <= lim for v, lim in checks.values())
            shown = " ".join(f"{k}={v}/{lim}" for k, (v, lim)
                             in checks.items())
            print(f"{args.workload} seed={seed} {label} correct={ok} "
                  f"{shown}", flush=True)
            if ctl and ok:
                passed += 1
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
