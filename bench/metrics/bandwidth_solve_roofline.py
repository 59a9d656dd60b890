"""bandwidth_solve_roofline: the share of its roofline that ``kernels/bandwidth_solve.py``'s kernels
(_bw_kernel) reached in the traced window (%).

Each call's least time is the larger of its FLOPs over the bf16 peak and
its operand and result bytes over the HBM bandwidth, both counted once per
call from the call's shapes in the compiled program; the share is the
calls' least time over their traced time.  A kernel that re-reads its
operands is counted once, so the share is a lower bound.

Since each greedy step solves its trials over the BSs' compact member
lists, a call reads about 150 KB: the share is per call and bound by
the call's latency, not the chip's bandwidth.  The Eq. (11) layer's time
is ``eq11_ms_per_pass.decision``'s.
"""
from bench import counting

KERNELS = ('_bw_kernel',)


def read(ctx):
    return counting.roofline_share(ctx, KERNELS)
