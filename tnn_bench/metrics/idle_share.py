"""Idle share of the device in the traced window (%)."""
from tnnbench.readers import idle_share as read  # noqa: F401
