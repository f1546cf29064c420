"""``host_loop_ms`` batch by batch: the median over the traced
``engine.serve`` spans of the device-idle milliseconds inside the
batch's ``serve.demand``, ``serve.respond_hits`` and
``serve.respond_misses`` spans. A pause of the process that lands in one
batch moves the mean and not this. None where the program has no such
spans."""
from host_copy_ms import idle_ms_median
from host_loop_ms import PHASES


def read(ctx):
    return idle_ms_median(ctx, PHASES)
