"""``serve_host_ms`` in the Moonlight cell."""
from serve_host_ms import read  # noqa: F401
