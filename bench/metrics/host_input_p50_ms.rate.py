"""``host_input_p50_ms`` in an open-loop cell, where it moves the latency
tail."""
from host_input_p50_ms import read  # noqa: F401
