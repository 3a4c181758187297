"""Share of its roofline that the btt kernels reach, in %: for every
execution of one of the kernels below in the traced window, the least time
its work needs (the larger of FLOPs over peak and bytes over HBM bandwidth,
``bench.workcount.call_work``, from shapes alone), summed, over the device
time of those executions.  Nothing when the trace holds none of them."""
from bench.workcount import family_share

KERNELS = ("btt_linear", "btt_backward", "btt_ffn_fwd", "btt_ffn_bwd")


def read(record):
    return family_share(record, KERNELS)
