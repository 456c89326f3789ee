"""Arithmetic shared by the readers of the program's phase counters
(the ``ServingEngine.metrics`` and ``AsyncServeLoop.metrics`` keys that
``repro.serve.telemetry.phase`` times). A program without the counter
gives no reading."""
from __future__ import annotations


def launch_gap_ms(run):
    """Mean ms from one serving step's result reaching the host to the
    next step's launch returning, over consecutive steps
    (``launch_gap_s`` over ``launch_gaps``, the window's deltas)."""
    r = run.record
    if r["kind"] != "lm" or not r["engine"].get("launch_gaps"):
        return None
    return 1e3 * r["engine"]["launch_gap_s"] / r["engine"]["launch_gaps"]
