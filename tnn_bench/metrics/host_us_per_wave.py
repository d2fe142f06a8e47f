"""Host loop time per wave, staging and step call (us)."""
from tnnbench.readers import host_us_per_wave as read  # noqa: F401
