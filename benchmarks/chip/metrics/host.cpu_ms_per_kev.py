"""host.cpu_ms_per_kev: the process's CPU time over the window
(``time.process_time``: every thread, the TPU runtime's included) per
thousand events whose chunk completed in the window."""


def read(run):
    done = sum(c[2] for c in run.completed())
    return 1e6 * run.cpu_s / done if done else None
