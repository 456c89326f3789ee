"""95th percentile of every gap between consecutive streamed tokens of a
request, over the gaps that end inside the window, in ms."""
from bench.stats import percentile


def read(run):
    r = run.record
    if r["kind"] != "lm":
        return None
    t0, t1 = run.window
    gaps = [b - a for q in r["reqs"] for a, b in zip(q.times, q.times[1:])
            if t0 <= b < t1]
    p = percentile(gaps, 0.95)
    return None if p is None else p * 1e3
