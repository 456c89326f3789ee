"""From a profiler trace of the window to per-layer numbers.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a plain
structure: per device, its operations and its program (module)
executions as ``(name, start_ns, end_ns)``; on the host, the harness's
own ``bench.*`` spans. ``reduce_events`` turns that into:

- ``busy_s``: the union of each device's operation intervals, averaged
  over the devices;
- ``programs``: per serving program named in ``bench/names.json``, its
  calls, device seconds (the sum of its module executions) and, per
  kernel named there, the device seconds of that kernel's operations
  inside those executions;
- ``ner_doc_device_s``: for the multi-service cell, per round of calls
  (one per device), the longest module execution;
- ``breakdown``: the ten operations that took the most device time, and
  the ten longest idle gaps of device 0, each named by the host span
  that covered most of it.

The names of programs and kernels are data (``bench/names.json``), so a
renamed program needs a new entry there and no code.
"""
from __future__ import annotations

import glob
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
# accelerator planes only: "/device:TPU:0", not "/device:CUSTOM:..."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def short(name: str) -> str:
    """An HLO operation's event name without its signature:
    ``%while.12 = (...) while(...)`` becomes ``while.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [(short(e.name), e.start_ns, e.end_ns)
                                for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    return {"devices": dict(sorted(devices.items())), "host": host}


def union_s(intervals) -> float:
    """Seconds covered by the union of (start_ns, end_ns) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def _gaps(intervals, top: int):
    """The ``top`` longest gaps between merged intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return sorted(gaps, reverse=True)[:top]


def _cover(spans, s, e) -> str:
    """The host span that overlaps [s, e) the most, or ``host: none``."""
    best, name = 0, "host: none"
    for n, hs, he in spans:
        o = min(e, he) - max(s, hs)
        if o > best:
            best, name = o, n
    return name


def _inside(ops, windows):
    """Operations whose start lies inside one of the sorted windows."""
    out, i = [], 0
    for op in sorted(ops, key=lambda o: o[1]):
        while i < len(windows) and windows[i][1] < op[1]:
            i += 1
        if i < len(windows) and windows[i][0] <= op[1] <= windows[i][1]:
            out.append(op)
    return out


def reduce_events(ev: dict, names: dict) -> dict:
    devs = list(ev["devices"].values())
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = [union_s([(s, e) for _, s, e in d["ops"]]) for d in devs]
    programs = {}
    for prog, pattern in names["programs"].items():
        rx = re.compile(pattern)
        calls, device_s, kernels = 0, 0.0, {}
        for d in devs:
            mods = [(s, e) for n, s, e in d["modules"] if rx.search(n)]
            calls += len(mods)
            device_s += sum(e - s for s, e in mods) / 1e9
            inside = _inside(d["ops"], sorted(mods))
            for kname, kpat in names["kernels"].items():
                krx = re.compile(kpat)
                kernels[kname] = kernels.get(kname, 0.0) + sum(
                    e - s for n, s, e in inside if krx.search(n)) / 1e9
        programs[prog] = {"calls": calls, "device_s": device_s,
                          "kernels": kernels}
    per_op: dict = {}
    for d in devs:
        for n, s, e in d["ops"]:
            per_op[n] = per_op.get(n, 0) + (e - s)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = _gaps([(s, e) for _, s, e in devs[0]["ops"]], 10)
    breakdown = {
        "device_ops": [[n, ns / 1e9 / len(devs)] for n, ns in top_ops],
        "idle_gaps": [[_cover(ev["host"], s, e), g / 1e9]
                      for g, s, e in gaps]}
    out = {"busy_s": sum(busy) / len(busy), "programs": programs,
           "breakdown": breakdown}
    ner = re.compile(names["programs"].get("ner", r"(?!)"))
    rounds = [[e - s for n, s, e in d["modules"] if ner.search(n)]
              for d in devs]
    if all(rounds):
        k = min(len(r) for r in rounds)
        out["ner_doc_device_s"] = [max(r[i] for r in rounds) / 1e9
                                   for i in range(k)]
    return out


def module_counts(ev: dict) -> dict:
    """Per device, how many times each program ran (names without the
    compile fingerprint): a first look when a name table misses."""
    out = {}
    for dev, d in ev["devices"].items():
        c: dict = {}
        for n, _, _ in d["modules"]:
            k = n.split("(")[0]
            c[k] = c.get(k, 0) + 1
        out[dev] = c
    return out


def reduce(trace_dir: str, names: dict, n_devices: int) -> dict:
    ev = load(trace_dir)
    if len(ev["devices"]) < n_devices:
        raise ValueError(f"trace holds {len(ev['devices'])} device planes, "
                         f"the cell used {n_devices}")
    out = reduce_events(ev, names)
    out["module_counts"] = module_counts(ev)
    return out
