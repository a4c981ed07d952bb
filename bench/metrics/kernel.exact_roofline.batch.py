"""kernel.exact_roofline.batch: the exact-phase kernels' share of their
roofline, in percent (moves qps): the least time the chip could take over
the work the engine reports (bench/roofline.py: distances evaluated,
corpus rows needed, queries and answers), over the summed device time of
the exact-phase kernel events in the traced window.

Matched trace events (regexes on the HLO instruction names of the
XLA Ops line): %masked_pairwise_kernel_call, the custom call of
kernels/pairwise_dist.py::masked_pairwise_kernel_call that runs the
masked exact phase for l2 (MXU) and JSD (VPU, the tile kernel of
kernels/jsd_dist.py), read from a v5e trace.  A later rename of those
kernels is repaired here.
"""

from bench import roofline

MATCH = [r"^%masked_pairwise_kernel_call\b"]


def read(ctx):
    return roofline.share(ctx, MATCH)
