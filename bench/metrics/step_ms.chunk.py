"""Device ms per call of the chunk-window program (jit_paged_chunk_step),
from the trace."""
from bench.readers import program_ms


def read(run):
    return program_ms(run, "chunk")
