"""Share of the router's token-expert assignments that land on the
experts this chip holds: 100 × ``moe.rows_held`` / ``moe.assignments``,
the program's counters over every ``SimCacheEngine.serve`` prefill of
the process (padding rows included, as the device routes them; the
warm-up's direct prefill calls not). About held / routed experts where
the routing is even. None where the program keeps no such counters."""


from _moonlight import held_share


def read(ctx):
    share = held_share(ctx)
    return None if share is None else 100.0 * share
