"""select_topk_roofline: the share of its roofline that ``kernels/select_topk.py``'s kernels
(_select_kernel, _rowmax_kernel) reached in the traced window (%).

Each call's least time is the larger of its FLOPs over the bf16 peak and
its operand and result bytes over the HBM bandwidth, both counted once per
call from the call's shapes in the compiled program; the share is the
calls' least time over their traced time.  A kernel that re-reads its
operands is counted once, so the share is a lower bound.
"""
from bench import counting

KERNELS = ('_select_kernel', '_rowmax_kernel')


def read(ctx):
    return counting.roofline_share(ctx, KERNELS)
