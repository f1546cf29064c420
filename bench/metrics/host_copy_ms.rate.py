"""``host_copy_ms`` in an open-loop cell, where it moves the latency
tail."""
from host_copy_ms import read  # noqa: F401
