"""The core with a 4-entry bitstream cache and its pinned cell: its
files against the configuration they copy, sound and faulty runs on the
CPU at a tiny size, and the reader of the bitstream-level share."""
from __future__ import annotations

import json
import os
from types import SimpleNamespace

import pytest

from conftest import ROOT, run_cell
from bench import run


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _reader():
    return run.load_module(os.path.join(ROOT, "bench", "metrics",
                                        "bs_level_cells_pct.sweep.py"))


def test_config_copies_embench_core_but_the_cache():
    """Every key of `embench-core` but the bitstream cache's capacity,
    and the texts that say so, is copied; the file states its cut and
    the sizes it assumes as `BENCHMARK.json` does."""
    core = _json("bench", "configs", "embench-core.json")
    new = _json("bench", "configs", "embench-bscache4.json")
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "embench-bscache4")
    assert new["name"] == entry["name"]
    assert new["reduced"] == entry["reduced"] == []
    assert new["source"] == entry["source"]
    texts = {"name", "source", "about", "assumed", "core"}
    assert {k: v for k, v in new.items() if k not in texts} == \
        {k: v for k, v in core.items() if k not in texts}
    assert new["core"] == {**core["core"], "bs_cache_entries": 4}
    assert new["assumed"]["bs_cache_entries"] == 4
    assert new["assumed"]["bs_miss_extra"] == core["core"]["bs_miss_extra"]
    # smaller than the slot tags the alphabet holds
    tags = {row[3] for row in new["instructions"]} - {-1}
    assert new["core"]["bs_cache_entries"] < len(tags)


def test_pinned_traffic_is_fig7_with_its_engine():
    fig7 = _json("bench", "traffic", "fig7.json")
    pinned = _json("bench", "traffic", "fig7.interleaved.json")
    assert {k: v for k, v in pinned.items() if k != "about"} == {
        **{k: v for k, v in fig7.items() if k != "about"},
        "kind": "sweep_fleet_pinned", "path": "interleaved"}


def test_sound_run_reads_every_cell_on_the_level(tiny, capsys, monkeypatch):
    from repro.core import simulator

    # the counters hold the whole process's sweeps, as in a run of its own
    monkeypatch.setattr(simulator, "_cells_real", 0)
    monkeypatch.setattr(simulator, "_cells_launched", 0)
    monkeypatch.setattr(simulator, "_cells_by_engine",
                        dict.fromkeys(simulator._cells_by_engine, 0))
    res = run_cell(tiny, "fig7.bscache4", 2900000021, trace=1,
                   capsys=capsys)
    assert res["correct"]
    got = res["metrics"]
    assert got["bs_level_cells_pct.sweep"]["value"] == 100.0
    assert got["pad_cells_pct.sweep"]["value"] == pytest.approx(
        100.0 * 2 / 52)


def test_warm_rule_in_place_of_the_level_is_caught(tiny, capsys,
                                                   monkeypatch):
    """A program whose window pass charges bitstream misses by the warm
    rule (the cold touch only) on this core: counters differ from the
    reference."""
    from repro.core import stackdist_interleaved

    monkeypatch.setattr(stackdist_interleaved, "bs_level",
                        lambda bs_entries, num_tags: None)
    res = run_cell(tiny, "fig7.bscache4", 2900000022, capsys=capsys)
    assert not res["correct"]
    assert res["checks"]["counter_mismatches"]["value"] > 0


def test_a_program_without_the_level_fails_at_once(tiny, monkeypatch):
    """A program that refuses this grid on the interleaved engine, as one
    from before the level does for a cold cache, fails the pinned cell in
    its set-up instead of timing the scan."""
    from repro.core import simulator

    monkeypatch.setattr(simulator, "interleaved_eligible",
                        lambda **kw: False)
    from conftest import cpu_chips
    with pytest.raises(ValueError, match="interleaved path"):
        run.run(["--workload", "fig7.bscache4", "--seed", "5",
                 "--seconds", "1"], chips=cpu_chips, root=tiny)


@pytest.mark.parametrize("counts,want", [
    ({"cells_real": 300, "cells_launched": 312, "cells_bs_level": 312},
     100.0),
    ({"cells_real": 300, "cells_launched": 312, "cells_bs_level": 0}, 0.0),
    ({"cells_real": 600, "cells_launched": 624, "cells_bs_level": 156},
     25.0),
    ({"cells_real": 300, "cells_launched": 312}, None),
    ({"cells_real": 0, "cells_launched": 0, "cells_bs_level": 0}, None),
])
def test_reader_on_fixed_counters(monkeypatch, counts, want):
    from repro.core import simulator

    monkeypatch.setattr(simulator, "cell_counts", lambda: dict(counts))
    assert _reader().read(SimpleNamespace()) == want


def test_reader_without_counters(monkeypatch):
    from repro.core import simulator

    monkeypatch.delattr(simulator, "cell_counts")
    assert _reader().read(SimpleNamespace()) is None
