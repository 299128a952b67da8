"""Device time of the window kernel per grid, on the busiest chip.  Its
ops are the Pallas kernel's custom calls, which XLA names after the
jitted entry that holds them: `window_grid` (a sweep) or `window_cell`
(a resumed cell)."""


def is_kernel(op: str) -> bool:
    return (op.startswith(("%window_grid", "%window_cell"))
            and 'custom_call_target="tpu_custom_call"' in op)


def kernel_seconds(trace):
    if not trace.devices:
        return 0.0
    return max(trace.op_seconds(is_kernel, d) for d in trace.devices)


def read(ctx):
    s = kernel_seconds(ctx.trace)
    if s <= 0.0:
        return None
    return 1e3 * s / ctx.window.units
