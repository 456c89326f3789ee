"""90th percentile of the scheduler's queue wait (``SchedulerStats``
``queue_wait_s``: submit to admission) of requests admitted in the
window, in ms."""
from bench.stats import percentile


def read(run):
    r = run.record
    if r["kind"] != "lm":
        return None
    p = percentile(r["queue_waits_s"], 0.90)
    return None if p is None else p * 1e3
