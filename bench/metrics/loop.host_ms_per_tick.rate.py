"""Host ms per serve-loop tick, in the cells where it moves itl_p95_ms
(bench/readers.py)."""
from bench.readers import host_ms_per_tick as read  # noqa: F401
