"""Host ms per serve-loop tick inside ``ServingEngine.add_requests``,
the blocking admit program included (``ServingEngine.metrics``
``admit_s`` over ``AsyncServeLoop.metrics`` ``ticks``, the window's
deltas)."""


def read(run):
    r = run.record
    if r["kind"] != "lm" or "admit_s" not in r["engine"] \
            or not r["loop"]["ticks"]:
        return None
    return 1e3 * r["engine"]["admit_s"] / r["loop"]["ticks"]
