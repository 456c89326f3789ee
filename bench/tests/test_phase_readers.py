"""The readers of the program's phase counters, on the record of a
rehearsed run (``bench/rehearse.py``'s CPU sizes): each finds its
counters and reads a finite value, and reads nothing, without raising,
from a record whose program lacks them."""
import math

import pytest

from bench import rehearse, run as bench_run, spec
from bench.drivers import lm_serve

SEED = 2**31 + 13
READERS = {"qwen3-4b.decode-sat": ["loop.launch_gap_ms.sat",
                                   "loop.outside_ms_per_tick"],
           "deepseek-7b.extract-rate": ["loop.launch_gap_ms.rate",
                                        "engine.admit_ms_per_tick",
                                        "engine.admit_to_first_token_ms"]}


@pytest.mark.parametrize("workload", list(READERS))
def test_phase_readers_on_a_rehearsed_record(workload, monkeypatch):
    records = []
    real = lm_serve.run
    monkeypatch.setattr(lm_serve, "run",
                        lambda ctx: records.append(real(ctx)) or records[-1])
    cell = spec.cell(workload)
    args = bench_run.parse(["--workload", workload, "--seed", str(SEED),
                            "--seconds", "3"])
    result, run = bench_run.execute(
        args, rehearsal=True,
        config_override=rehearse.reduced(spec.config(cell["config"])),
        mix_override=rehearse.rehearse_mix(spec.traffic(cell["traffic"])))
    assert result["correct"]
    view = bench_run.View(run, records[0], None, None)
    listed = [m["name"] for m in spec.metrics_for(workload, per_layer=True)]
    for name in READERS[workload]:
        assert name in listed
        value = spec.reader(name)(view)
        assert value is not None and math.isfinite(value) and value >= 0, \
            (name, value)
    # the parent program's record: the counters are not there
    old = dict(records[0],
               engine={k: v for k, v in records[0]["engine"].items()
                       if k in ("decode_steps", "kernel_positions")},
               loop={k: v for k, v in records[0]["loop"].items()
                     if k in ("ticks", "commit_wait_s")})
    view = bench_run.View(run, old, None, None)
    for name in READERS[workload]:
        assert spec.reader(name)(view) is None, name
