#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration, traffic mix and metric readers are files under
``benchmarks/chip`` found by name.  Without a TPU, or without the program
(``src/repro``) beside the benchmark, the run exits non-zero and prints no
result.  The last line of standard output is the result object; the last
lines of standard error are the numbers compared for ``correct``, each with
its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr)
    return 2


def enable_compile_cache(repo: str) -> str:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``,
    else the fixed ``<checkout>/.jax_cache``).  The benchmark also keeps
    every program, however small or quick to compile: a run's set-up loads
    a few dozen small ones (the arena mirror's slices among them), and only
    a checkout's first run may compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable(repo)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, HERE)
    from chipbench import spec
    bench = spec.load_benchmark(REPO)
    cell = spec.find_cell(bench, args.workload)
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return fail(f"the program is not beside the benchmark ({src})")
    sys.path.insert(0, src)
    import jax
    cache = enable_compile_cache(REPO)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < int(cell["chips"]):
        return fail(f"the cell asks for {cell['chips']} chips, JAX found "
                    f"{len(devs)}")
    peaks = spec.load_peaks(devs[0].device_kind)
    print(f"[setup] compile cache {cache}; JAX has the devices at "
          f"{time.perf_counter() - T_START:.3f} s", flush=True)
    from chipbench import drive
    out, code = drive.execute(bench, cell, args.seed, args.seconds,
                              bool(args.trace), T_START, peaks=peaks)
    if code:
        return fail("the run measured nothing for an end-to-end metric")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
