"""Column forward kernel's share of its roofline, from required work (%):
the least time of the window's forward work over the device time of the
``tnn_column_forward`` kernel."""
from tnnbench import work_series


def read(run):
    return work_series.kernel_roofline(run, work_series.FORWARD_KERNEL)
