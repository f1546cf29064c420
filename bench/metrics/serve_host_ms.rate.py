"""``serve_host_ms`` in an open-loop cell, where it moves the latency
tail."""
from serve_host_ms import read  # noqa: F401
