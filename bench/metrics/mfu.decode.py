"""Model FLOPs of the window's decode-program tokens over the decode
program's device time times the chip's peak, in %."""
from bench.readers import mfu


def read(run):
    return mfu(run, "decode")
