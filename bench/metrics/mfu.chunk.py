"""Model FLOPs of the window's chunk-window tokens (prompt chunks and
their decode riders) over the chunk program's device time times the
chip's peak, in %."""
from bench.readers import mfu


def read(run):
    return mfu(run, "chunk")
