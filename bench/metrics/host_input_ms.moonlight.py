"""``host_input_ms`` in the Moonlight cell."""
from host_input_ms import read  # noqa: F401
