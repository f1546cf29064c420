"""``prefill_pad_share`` in the Moonlight cell."""
from prefill_pad_share import read  # noqa: F401
