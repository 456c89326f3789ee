"""The caller's ms per serve-loop tick: from the end of one ``run_once``
to the start of the next (``AsyncServeLoop.metrics`` ``outside_s`` over
``ticks``, the window's deltas). In the benchmark that is the harness's
own pump."""


def read(run):
    r = run.record
    if r["kind"] != "lm" or "outside_s" not in r["loop"] \
            or not r["loop"]["ticks"]:
        return None
    return 1e3 * r["loop"]["outside_s"] / r["loop"]["ticks"]
