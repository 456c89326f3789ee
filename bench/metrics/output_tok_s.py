"""Output tokens streamed to clients inside the window, over the
window's seconds: all the work and all the time of the window."""


def read(run):
    r = run.record
    if r["kind"] != "lm":
        return None
    t0, t1 = run.window
    n = sum(1 for q in r["reqs"] for t in q.times if t0 <= t < t1)
    return n / run.window_s
