"""The grouped expert matmul's device time over the device's busy time
in the traced sub-window (%), its op events picked by the architecture's
``expert_matmul_op``."""
from chipbench import experts


def read(r):
    return experts.time_share(r)
