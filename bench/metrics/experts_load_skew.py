"""How much more the busiest held expert computes than the mean held
expert: 100 × (busiest / mean − 1), from the program's counters over
every served prefill of the process. ``moe.rows_busiest`` sums, over
prefills and MoE layers, the rows of that layer's busiest held expert;
the mean held expert's is ``moe.rows_held`` over the experts held. A
grouped matmul takes as long as its busiest expert where the experts
run side by side. None where the program keeps no such counters."""


def read(ctx):
    from repro import tracecount
    held_rows = tracecount.get("moe.rows_held")
    if not held_rows:
        return None
    mean = held_rows / int(ctx.cfg["n_routed_experts"])
    return 100.0 * (tracecount.get("moe.rows_busiest") / mean - 1.0)
