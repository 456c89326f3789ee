"""90th percentile of the time to first token over every request due in
the window, timed from when it was due, in ms. A request still without
a token when the drain after the window ends counts with the time until
then."""
from bench.stats import percentile


def read(run):
    r = run.record
    if r["kind"] != "lm" or r["traffic_loop"] != "poisson":
        return None
    ttft = [(q.times[0] if q.times else r["drain_end"]) - q.due
            for q in r["due"]]
    p = percentile(ttft, 0.90)
    return None if p is None else p * 1e3
