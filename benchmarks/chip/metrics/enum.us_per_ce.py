"""enum.us_per_ce: host clock around the benchmark sink's
``enumerate_hits`` call (delta fetch + Algorithm 2) per complex event it
listed, over the sink calls of the window."""


def read(run):
    calls = [c for c in run.enum_calls if c[0] <= run.t_close]
    n = sum(c[2] for c in calls)
    return 1e6 * sum(c[1] for c in calls) / n if n else None
