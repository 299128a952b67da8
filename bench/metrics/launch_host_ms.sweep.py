"""Host time per grid inside the dispatcher's `sim.launch` spans (each call
into an engine, up to its return: the engine's host dispatch), summed
over the traced window, over its grids."""
from bench import spans


def read(ctx):
    return spans.host_ms_per_unit(ctx, "sim.launch")
