"""``host_input_p50_ms`` in the Moonlight cell."""
from host_input_p50_ms import read  # noqa: F401
