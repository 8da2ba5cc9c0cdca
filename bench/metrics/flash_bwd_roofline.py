"""Flash backward, the dQ and dK/dV kernels together: least time of their
work over their device time (%).

Work per backward from ``flops.flash_bwd_work`` (the backward's own four
matmuls per pair, 8 * heads * head_dim FLOPs, without the rebuilt QK^T).
Both kernels appear in the trace as ``terapipe_attention_bwd`` events
(named after the program's wrapper function); one backward is two of
them, the dQ kernel and the dK/dV kernel."""
import flops
from metrics._kernels import roofline_share

PATTERN = r"^terapipe_attention_bwd\b"


def read(obs):
    return roofline_share(obs, flops.flash_bwd_work, PATTERN, per_call=2)
