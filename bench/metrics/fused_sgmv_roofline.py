"""Least time of the work the window's fused_sgmv calls needed (real rows
times the base weight and each row's adapter, each weight read once) over
the kernel's summed device time in the trace."""

from perfkit import readers


def read(run):
    return readers.kernel_roofline(run, "fused_sgmv", readers.fused_sgmv_least_s)
