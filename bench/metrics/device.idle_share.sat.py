"""Share of the traced window with no operation on the device, in the
cells where it moves output_tok_s (bench/readers.py)."""
from bench.readers import idle_share as read  # noqa: F401
