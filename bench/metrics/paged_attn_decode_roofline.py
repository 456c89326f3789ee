"""The paged-attention kernel's share of its roofline inside the decode
program: held-token work at the chip's peaks over the kernel's device
time, in %."""
from bench.readers import kernel_roofline


def read(run):
    return kernel_roofline(run, "paged_attn", "decode")
