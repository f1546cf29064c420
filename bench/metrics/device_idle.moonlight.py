"""``device_idle`` in the Moonlight cell."""
from device_idle import read  # noqa: F401
