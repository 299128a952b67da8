"""Host time per grid inside the dispatcher's `sim.assemble` spans (the
result put together: the chunks concatenated and the padded rows sliced
off, or the quantum axis broadcast or dropped), summed over the traced
window, over its grids."""
from bench import spans


def read(ctx):
    return spans.host_ms_per_unit(ctx, "sim.assemble")
