"""Wave kernel's share of its roofline, from required work (%)."""
from tnnbench.readers import wave_roofline as read  # noqa: F401
