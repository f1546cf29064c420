"""``device_idle`` in an open-loop cell, where it moves the latency
tail."""
from device_idle import read  # noqa: F401
