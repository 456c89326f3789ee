"""Host ms per serve-loop tick, in the cells where it moves
output_tok_s (bench/readers.py)."""
from bench.readers import host_ms_per_tick as read  # noqa: F401
