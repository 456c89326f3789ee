"""Share of the traced window with no operation on the device, averaged
over the four chips, in the cells where it moves doc_p99_ms
(bench/readers.py)."""
from bench.readers import idle_share as read  # noqa: F401
