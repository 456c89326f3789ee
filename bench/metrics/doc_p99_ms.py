"""99th percentile of per-document latency, from sending the document
(routing and tokenizing included) to its labels joined on the host, over
documents sent in the window, in ms."""
from bench.stats import percentile


def read(run):
    r = run.record
    if r["kind"] != "ner":
        return None
    t0, t1 = run.window
    lat = [b - a for a, b in r["docs"] if t0 <= a < t1]
    p = percentile(lat, 0.99)
    return None if p is None else p * 1e3
