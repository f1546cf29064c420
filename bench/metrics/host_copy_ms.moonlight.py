"""``host_copy_ms`` in the Moonlight cell."""
from host_copy_ms import read  # noqa: F401
