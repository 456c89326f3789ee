"""Arithmetic shared by the metric readers in ``bench/metrics/``: each
reader names one quantity for one group of cells, these compute it."""
from __future__ import annotations

from bench.work import roofline_share


def host_ms_per_tick(run):
    """Host ms per serve-loop tick: time inside ``run_once`` less the
    time it waited on the device (``AsyncServeLoop.metrics``
    ``commit_wait_s``), over the window's ticks."""
    r = run.record
    if r["kind"] != "lm" or not r["loop"]["ticks"]:
        return None
    host = r["loop_time_s"] - r["loop"]["commit_wait_s"]
    return 1e3 * host / r["loop"]["ticks"]


def program_ms(run, program: str):
    """Mean device ms per call of one serving program, from the trace."""
    p = (run.trace or {}).get("programs", {}).get(program)
    if not p or not p["calls"]:
        return None
    return 1e3 * p["device_s"] / p["calls"]


def mfu(run, program: str):
    """Model FLOPs of the window's tokens through one program, over that
    program's device time times the chip's peak, in %."""
    p = (run.trace or {}).get("programs", {}).get(program)
    w = run.record.get("work")
    if not p or not p["device_s"] or w is None or not w[program].model_flops:
        return None
    return 100.0 * w[program].model_flops / (
        p["device_s"] * run.peaks["bf16_flops"])


def kernel_roofline(run, kernel: str, program: str):
    """A kernel's share of its roofline inside one program: the held-token
    work (``bench/work.py``) at the chip's peaks, over the kernel's
    device time in that program's calls, in %."""
    p = (run.trace or {}).get("programs", {}).get(program)
    w = run.record.get("work")
    if not p or w is None or not w[program].attn_flops:
        return None
    seconds = p["kernels"].get(kernel, 0.0)
    if not seconds:
        return None
    share, _ = roofline_share(w[program].attn_flops,
                              w[program].kernel_bytes, seconds, run.peaks)
    return share


def idle_share(run):
    """1 - device busy time (the union of the device's operations,
    averaged over the chips used) over the traced window, in %."""
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace_s)
