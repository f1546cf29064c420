"""``host_loop_ms`` in an open-loop cell, where it moves the latency
tail."""
from host_loop_ms import read  # noqa: F401
