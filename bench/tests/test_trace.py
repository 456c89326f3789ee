"""The trace reduction: busy and idle arithmetic, per-program device
time, a kernel's time inside its programs, and the breakdown."""
import pytest

from bench import trace

NAMES = {"programs": {"decode": "paged_decode_step",
                      "chunk": "paged_chunk_step", "ner": "_ner_step"},
         "kernels": {"paged_attn": "paged_window"}}


def ev(ms_start, ms_end, name="op"):
    return (name, int(ms_start * 1e6), int(ms_end * 1e6))


def test_union_merges_overlaps():
    assert trace.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert trace.union_s([]) == 0.0


def test_programs_kernels_and_breakdown():
    dev = {"modules": [ev(0, 10, "jit_paged_decode_step(1)"),
                       ev(22, 40, "jit_paged_chunk_step(2)"),
                       ev(50, 60, "jit_paged_decode_step(1)")],
           "ops": [ev(0, 6, "paged_window_kernel"), ev(6, 10, "fusion.1"),
                   ev(22, 35, "paged_window_kernel"), ev(35, 40, "fusion.2"),
                   ev(50, 57, "paged_window_kernel"), ev(57, 60, "fusion.1")]}
    host = [ev(10, 22, "bench.run_once"), ev(40, 50, "bench.submit")]
    out = trace.reduce_events({"devices": {"/device:TPU:0": dev},
                               "host": host}, NAMES)
    assert out["busy_s"] == pytest.approx(0.038)
    dec, chk = out["programs"]["decode"], out["programs"]["chunk"]
    assert dec["calls"] == 2 and dec["device_s"] == pytest.approx(0.020)
    assert dec["kernels"]["paged_attn"] == pytest.approx(0.013)
    assert chk["calls"] == 1 and chk["kernels"]["paged_attn"] == \
        pytest.approx(0.013)
    ops = dict((n, s) for n, s in out["breakdown"]["device_ops"])
    assert ops["paged_window_kernel"] == pytest.approx(0.026)
    gaps = out["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["bench.run_once", "bench.submit"]
    assert gaps[0][1] == pytest.approx(0.012)


def test_busy_is_averaged_over_devices_and_rounds_take_the_straggler():
    a = {"modules": [ev(0, 2, "jit__ner_step"), ev(10, 13, "jit__ner_step")],
         "ops": [ev(0, 2), ev(10, 13)]}
    b = {"modules": [ev(0, 4, "jit__ner_step"), ev(10, 11, "jit__ner_step")],
         "ops": [ev(0, 4), ev(10, 11)]}
    out = trace.reduce_events({"devices": {"/device:TPU:0": a,
                                           "/device:TPU:1": b},
                               "host": []}, NAMES)
    assert out["busy_s"] == pytest.approx(0.005)
    assert out["ner_doc_device_s"] == pytest.approx([0.004, 0.003])


def test_a_trace_without_device_planes_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_events({"devices": {}, "host": []}, NAMES)


def recorded():
    """Five module executions of a qwen3-4b.decode-sat window (two
    block writes, two chunk windows, one decode step), recorded on a TPU
    v5e and cut from the profile by ``bench.trace.load``."""
    import gzip
    import json
    from pathlib import Path
    path = Path(__file__).with_name("data") / "qwen3_decode_chunk_trace.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_chip_trace():
    from bench import spec
    from bench.work import roofline_share
    ev = recorded()
    out = trace.reduce_events(ev, spec.names())
    mods = ev["devices"]["/device:TPU:0"]["modules"]
    # the operations cover nearly all of the module executions
    in_modules = sum(e - s for _, s, e in mods) / 1e9
    assert 0.95 * in_modules < out["busy_s"] <= in_modules
    assert out["busy_s"] == pytest.approx(0.8290432, rel=1e-6)
    dec, chk = out["programs"]["decode"], out["programs"]["chunk"]
    assert (dec["calls"], chk["calls"]) == (1, 2)
    assert dec["device_s"] == pytest.approx(0.2006793, rel=1e-6)
    # the paged kernel is most of the decode step: 36 calls of 4.53 ms
    assert dec["kernels"]["paged_attn"] == pytest.approx(0.1631530, rel=1e-6)
    assert chk["kernels"]["paged_attn"] < chk["device_s"]
    # a roofline share from that kernel time: 1 GB of held KV is 1.22 ms
    # at 819 GB/s, 0.75% of 163 ms
    share, bound = roofline_share(1e9, 1e9, dec["kernels"]["paged_attn"],
                                  spec.peaks("TPU v5 lite"))
    assert bound == "memory" and share == pytest.approx(0.7484, rel=1e-3)
    # idle time between steps is the host's serve-loop tick
    assert all(name == "bench.run_once"
               for name, _ in out["breakdown"]["idle_gaps"])
    assert out["breakdown"]["device_ops"][0][0].startswith("while")
