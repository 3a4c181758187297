"""Share of its roofline that the update kernels reach, in %: for every
execution of one of the kernels below in the traced window, the least time
its work needs (the larger of FLOPs over peak and bytes over HBM bandwidth,
``bench.workcount.call_work``, from shapes alone), summed, over the device
time of those executions.  Nothing when the trace holds none of them."""
from bench.workcount import family_share

KERNELS = ("fused_sgd", "fused_adamw")


def read(record):
    return family_share(record, KERNELS)
