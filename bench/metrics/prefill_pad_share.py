"""Share of the miss prefill's rows that are padding: 100 × (1 −
``prefill.rows_valid`` / ``prefill.rows``), the program's counters of
the misses and of the rows of their power-of-two bucket, summed over
every ``SimCacheEngine.serve`` call of the process. That includes the
warm-up's one call per batch size the window sends (1 of ~200 batches
in resp-1m.mix, at most 11 of ~2,500 in retr-1m.hot-rate); the
warm-up's direct prefill calls are not counted. None where the program
keeps no such counters."""


def read(ctx):
    from repro import tracecount
    rows = tracecount.get("prefill.rows")
    if not rows:
        return None
    return 100.0 * (1.0 - tracecount.get("prefill.rows_valid") / rows)
