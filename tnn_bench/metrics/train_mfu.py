"""Training step's share of the int8 peak (%)."""
from tnnbench.readers import step_mfu as read  # noqa: F401
