"""The control (the reference with FIFO in place of LRU, put in the
program's place) fails every cell's comparison, and the program passes
it, at a size a CPU run holds."""
from __future__ import annotations

import pytest

from conftest import cpu_chips


@pytest.mark.parametrize("workload", ["fig7.preempted", "bitstream.solo"])
def test_control_fails_the_comparison(tiny, workload):
    from bench import control

    for row in control.control(workload, [21, 22, 23], 2, chips=cpu_chips,
                               root=tiny):
        assert all(v <= row["limits"][n] for n, v in row["program"].items())
        assert any(v > row["limits"][n] for n, v in row["control"].items())
