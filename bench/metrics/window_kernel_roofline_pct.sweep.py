"""The window kernel's share of its roofline: the least time its bytes
take at the HBM bandwidth of the cell's chips, over the kernel's device
time on the busiest chip.  The bytes are the grid's streams and counters
from its shape (`bench/work.py`); the kernel does integer work that no
published peak covers, so HBM is the bound."""
import importlib.util
import os

from bench import peaks

_here = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "bench_window_kernel_ms", os.path.join(_here, "window_kernel_ms.sweep.py"))
_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ms)


def read(ctx):
    s = _ms.kernel_seconds(ctx.trace)
    if s <= 0.0:
        return None
    bandwidth = len(ctx.trace.devices) * peaks.peak(ctx.device_kind,
                                                    "hbm_bytes_per_s")
    least = ctx.cell.kernel_bytes * ctx.window.units / bandwidth
    return 100.0 * least / s
