"""``host_loop_ms`` in the Moonlight cell."""
from host_loop_ms import read  # noqa: F401
