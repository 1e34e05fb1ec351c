"""The grouped expert matmul's share of its roofline in the traced
sub-window (%): the least time the chip could take for the work the
program's spans recorded there (the larger of its FLOPs at the bf16 peak
and its bytes at the HBM bandwidth, the architecture's
``expert_matmul_bound_s``) over the device time of the kernel's op
events (its ``expert_matmul_op``)."""
from chipbench import experts


def read(r):
    return experts.roofline_share(r)
