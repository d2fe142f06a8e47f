"""STDP kernel's share of its roofline, from required work (%): the least
time of the window's STDP work over the device time of the
``tnn_stdp_update`` kernel."""
from tnnbench import work_series


def read(run):
    return work_series.kernel_roofline(run, work_series.STDP_KERNEL)
