"""Device ms per call of the decode program (jit_paged_decode_step),
from the trace."""
from bench.readers import program_ms


def read(run):
    return program_ms(run, "decode")
