"""Host time per grid inside the dispatcher's `sim.stage` spans (each
engine's inputs put in its form: the fleet chunks sliced and padded, the
arguments converted), summed over the traced window, over its grids."""
from bench import spans


def read(ctx):
    return spans.host_ms_per_unit(ctx, "sim.stage")
