"""Mean launch gap, the program's own measure of the time no serving
step is queued, in the cells where it moves output_tok_s
(bench/phase_counters.py)."""
from bench.phase_counters import launch_gap_ms as read  # noqa: F401
