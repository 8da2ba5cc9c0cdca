"""Flash forward kernel: least time of its work over its device time (%).

Work per call from ``flops.flash_fwd_work`` over the step's slice plan,
without block padding; the forward that remat recomputes counts as the
work it is.  Calls and time are the trace's ``terapipe_attention_fwd``
kernel events (named after the program's wrapper function)."""
import flops
from metrics._kernels import roofline_share

PATTERN = r"^terapipe_attention_fwd\b"


def read(obs):
    return roofline_share(obs, flops.flash_fwd_work, PATTERN)
