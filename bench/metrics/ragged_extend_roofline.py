"""Least time of the work the window's ragged_extend calls needed (FLOPs and
bytes from each row's real tokens and context) over the kernel's summed
device time in the trace."""

from perfkit import readers


def read(run):
    return readers.kernel_roofline(run, "ragged_extend", readers.ragged_extend_least_s)
