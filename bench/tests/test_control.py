"""The controls, at a size a test run can hold: ``bench/control.py`` on
the CPU rehearsal sizes, three seeds each. The control (the plain
reference one precision step below the configuration's: fp8 matmuls for
the bf16 language models) must read far above the program. On the chip,
at the cells' own sizes, the same script set the limits (PERF.md)."""
import json

import pytest

from bench import control


@pytest.mark.parametrize("workload, key", [
    ("qwen3-4b.decode-sat", "max_logit_gap"),
    ("deepseek-7b.extract-rate", "max_logit_gap"),
])
def test_control_reads_far_above_the_program(workload, key, capsys):
    assert control.main(["--workload", workload, "--seeds", "5,6,7",
                         "--seconds", "3", "--control-seeds", "3",
                         "--rehearsal"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["seeds"] == 3 and len(summary["control"]) == 3
    assert summary["control_min"] > 0
    assert summary["control_min"] >= 3 * summary["program_max"]
