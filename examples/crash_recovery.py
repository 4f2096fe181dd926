"""Crash-recovery smoke: kill -9 a streaming worker between chunks, restart
it on the same recovery directory, and assert the cumulative emitted match
set is bit-identical to an uninterrupted run (DESIGN.md §10).

    PYTHONPATH=src python examples/crash_recovery.py

Three worker subprocesses run the same deterministic PARTITION BY workload
(NULL keys and missing attrs included, tECS arena on); the parent never
initializes JAX, so on an accelerator each worker gets the device in turn:

1. an *oracle* worker that never crashes;
2. a worker that checkpoints every 4 chunks and SIGKILLs itself
   mid-interval (after chunk 11: checkpoints at 4 and 8, emission log
   through 10 — the checkpoint is deliberately BEHIND the log);
3. the same worker restarted: it resumes from the newest checkpoint,
   re-feeds chunks 8..10 with emission suppressed by the durable
   high-water mark, then completes the stream.

scripts/check.sh runs this as the fault-tolerance smoke.  Exit is nonzero
if the worker survives the kill, the restart fails, or the cumulative
match sets differ.
"""
import argparse
import os
import signal
import subprocess
import sys
import tempfile

QTEXT = "SELECT * FROM S WHERE A ; B+ ; C WITHIN 5 events"
TOTAL, CHUNK, EVERY, CRASH_AFTER = 320, 16, 4, 11


def make_stream():
    import random

    from repro.core import Event
    rng = random.Random(9)
    return [Event(rng.choice("ABCX"),
                  {} if rng.random() < 0.05
                  else {"uid": rng.choice(["u1", "u2", 7, None])})
            for _ in range(TOTAL)]


def make_engine():
    from repro.vector import PartitionedStreamingEngine, VectorEngine
    return PartitionedStreamingEngine(
        VectorEngine(QTEXT, use_pallas=False), ("uid",), chunk_len=CHUNK,
        num_lanes=8, arena_capacity=1 << 12)


def run_worker(directory: str, crash_after: int) -> None:
    from repro.runtime import RecoveringStreamRunner
    stream = make_stream()
    chunks = [stream[lo:lo + CHUNK] for lo in range(0, TOTAL, CHUNK)]
    runner = RecoveringStreamRunner(make_engine(), directory, every=EVERY)
    resumed = runner.resume()
    print(f"worker: {'resumed at chunk %d' % runner.chunk_index if resumed else 'fresh start'}",
          flush=True)
    for ch in chunks[runner.chunk_index:]:
        runner.process(ch)
        if runner.chunk_index == crash_after:
            print(f"worker: kill -9 after chunk {crash_after - 1}",
                  flush=True)
            os.kill(os.getpid(), signal.SIGKILL)   # no close(), no cleanup
    runner.close()
    print(f"worker: completed all {len(chunks)} chunks", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", metavar="DIR", default=None)
    ap.add_argument("--crash-after", type=int, default=-1)
    args = ap.parse_args()
    if args.worker:
        run_worker(args.worker, args.crash_after)
        return

    # reads the emission logs only: importing it initializes no JAX backend
    from repro.runtime.recovery import cumulative_matches
    worker = [sys.executable, os.path.abspath(__file__), "--worker"]
    with tempfile.TemporaryDirectory() as tmp:
        d_ref = os.path.join(tmp, "uninterrupted")
        p = subprocess.run(worker + [d_ref])
        if p.returncode != 0:
            sys.exit(f"oracle worker failed: rc={p.returncode}")
        oracle = cumulative_matches(d_ref)
        assert oracle["hits"], "workload produced no matches"

        d = os.path.join(tmp, "crashed")
        cmd = worker + [d]
        p = subprocess.run(cmd + ["--crash-after", str(CRASH_AFTER)])
        if p.returncode != -signal.SIGKILL:
            sys.exit(f"expected the worker to die by SIGKILL, "
                     f"got rc={p.returncode}")
        p = subprocess.run(cmd)
        if p.returncode != 0:
            sys.exit(f"restarted worker failed: rc={p.returncode}")
        got = cumulative_matches(d)
        if got != oracle:
            sys.exit("cumulative match set after kill -9 + restart differs "
                     "from the uninterrupted run — exactly-once replay is "
                     "broken")
        print(f"crash recovery OK: SIGKILL after chunk {CRASH_AFTER - 1}, "
              f"restart resumed from the checkpoint and re-emitted nothing; "
              f"{len(oracle['hits'])} hit positions bit-identical to the "
              f"uninterrupted run")


if __name__ == "__main__":
    main()
