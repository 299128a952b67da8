"""Host time per grid inside the dispatcher's `sim.plan` spans (input
checks, the tag table, the eligibility predicates and the path choice),
summed over the traced window, over its grids."""
from bench import spans


def read(ctx):
    return spans.host_ms_per_unit(ctx, "sim.plan")
