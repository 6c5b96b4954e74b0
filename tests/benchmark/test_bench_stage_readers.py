"""The readers of the native driver's stage clocks: each gives its
number on a hand-built context, and nothing on a program that exports
no such counter."""

from pathlib import Path

import pytest

from benchmark import spec
from benchmark.harness import Context

ROOT = Path(__file__).resolve().parents[2]

BEFORE = {
    "throttlecrab_tpu_device_launches": 100.0,
    "throttlecrab_tpu_wire_wait_seconds_total": 1.0,
    "throttlecrab_tpu_driver_busy_seconds_total": 2.0,
    "throttlecrab_tpu_front_seconds_total": 0.5,
    "throttlecrab_tpu_keymap_seconds_total": 0.25,
    "throttlecrab_tpu_fetch_seconds_total": 0.125,
    "throttlecrab_process_gc_seconds_total": 0.0,
}
# Over a 10 s window: 40 launches; the driver waits 1.5 s and is busy
# 8.5 s, of which front 2 s, keymap 1 s, fetch 0.5 s; GC takes 0.3 s.
AFTER = {
    "throttlecrab_tpu_device_launches": 140.0,
    "throttlecrab_tpu_wire_wait_seconds_total": 2.5,
    "throttlecrab_tpu_driver_busy_seconds_total": 10.5,
    "throttlecrab_tpu_front_seconds_total": 2.5,
    "throttlecrab_tpu_keymap_seconds_total": 1.25,
    "throttlecrab_tpu_fetch_seconds_total": 0.625,
    "throttlecrab_process_gc_seconds_total": 0.3,
}
EXPECTED = {
    "front.us_per_launch": 50_000.0,       # 1e6 * 2 / 40
    "keymap.us_per_launch": 25_000.0,      # 1e6 * 1 / 40
    "driver.fetch_us_per_launch": 12_500.0,  # 1e6 * 0.5 / 40
    "driver.self_us_per_launch": 125_000.0,  # 1e6 * (8.5 - 3.5) / 40
    "process.gc_share": 3.0,               # 100 * 0.3 / 10
}
CELLS = {".sat": "gw-1m.zipf-sat", ".paced": "api-10k.paced"}
NAMES = [(stem + suffix, cell) for stem in EXPECTED
         for suffix, cell in CELLS.items()]


def _ctx(before, after):
    return Context(counters_before=before, counters_after=after,
                   window_s=10.0)


@pytest.mark.parametrize("name, cell", NAMES)
def test_stage_reader_on_a_hand_built_context(name, cell):
    reader = spec.Cell(ROOT, cell).reader(name)
    stem = name.rpartition(".")[0]
    assert reader.__file__.endswith(f"/metrics/{stem}.py")
    assert reader.read(_ctx(BEFORE, AFTER)) == pytest.approx(EXPECTED[stem])


@pytest.mark.parametrize("name, cell", NAMES)
def test_stage_reader_reads_nothing_without_the_counters(name, cell):
    """A program without the stage clocks (the parent of this change)
    exports only the launch counter: the line leaves the metric out."""
    launches = "throttlecrab_tpu_device_launches"
    old = _ctx({launches: 100.0}, {launches: 140.0})
    assert spec.Cell(ROOT, cell).reader(name).read(old) is None


@pytest.mark.parametrize("stem", [s for s in EXPECTED if "per_launch" in s])
def test_per_launch_readers_read_nothing_without_a_launch(stem):
    idle = dict(AFTER, throttlecrab_tpu_device_launches=100.0)
    reader = spec.Cell(ROOT, "api-10k.paced").reader(stem + ".paced")
    assert reader.read(_ctx(BEFORE, idle)) is None


def test_every_stage_metric_is_declared_for_its_cell():
    for name, cell in NAMES:
        declared = [m["name"] for m in
                    spec.Cell(ROOT, cell).metrics("per_layer")]
        assert name in declared
        other = [c for c in CELLS.values() if c != cell][0]
        assert name not in [m["name"] for m in
                            spec.Cell(ROOT, other).metrics("per_layer")]
