"""Median device time of one call of the batched prefill program
(``model_zoo.prefill``), the mean over the cell's chips."""
import statistics

from bench import trace

ROLE = "prefill"


def read(ctx):
    module = trace.program(ctx.trace, ROLE)
    if module is None:
        return None
    per_chip = [trace.module_calls(ctx.trace, d, module)
                for d in ctx.trace.devices]
    per_chip = [c for c in per_chip if c]
    if not per_chip:
        return None
    return 1e3 * sum(statistics.median(c) for c in per_chip) / len(per_chip)
