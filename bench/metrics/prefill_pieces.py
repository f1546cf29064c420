"""Prefills a miss batch runs as: ``prefill.pieces`` / ``prefill.batches``,
the program's counters of the power-of-two prefills dispatched and of
the ``SimCacheEngine.serve`` calls that prefilled, summed over every
such call of the process (the warm-up's served batches included, its
direct prefill calls not). 1 where every miss batch runs as its one
bucket. None where the program keeps no such counters."""


def read(ctx):
    from repro import tracecount
    batches = tracecount.get("prefill.batches")
    if not batches:
        return None
    return tracecount.get("prefill.pieces") / batches
