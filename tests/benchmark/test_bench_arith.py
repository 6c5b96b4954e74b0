"""The benchmark's own arithmetic: needed bytes, peaks, percentiles and
spreads, the request streams, reply parsing, the reference and its
comparison, the trace reduction, and finding a cell's files by name."""

import json
import math
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import spec, stats, trace_reduce, workload
from benchmark.reference import clock, gcra

ROOT = Path(__file__).resolve().parents[2]
GW = json.loads((ROOT / "benchmark/configs/gw-1m.json").read_text())


def test_needed_bytes_are_56_per_decision():
    roof = spec.load_reader(ROOT / "benchmark/metrics/gcra_roofline.py")
    assert roof.BYTES_PER_DECISION == 56
    assert roof.needed_bytes(1000) == 56_000


def test_peaks_refuse_an_unknown_device_kind():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v4")
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def test_percentiles_count_unanswered_requests_as_missing():
    due = np.array([0, 0, 0, 0], np.int64)
    arrival = np.array([1_000_000, 2_000_000, 3_000_000, 0], np.int64)
    answered = np.array([True, True, True, False])
    lat = stats.latencies_ns(due, arrival, answered)
    assert math.isinf(lat[3])
    assert stats.percentile(lat, 50) == 2_000_000
    assert stats.percentile(lat, 75) == 3_000_000
    assert math.isinf(stats.percentile(lat, 99))
    # Nearest rank over 100 samples: p99 is the 99th smallest.
    assert stats.percentile(np.arange(1, 101), 99) == 99
    assert stats.percentile(np.arange(1, 101), 50) == 50


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([10, 10, 10, 10]) == 0
    q1, q2, q3 = 9.25, 10.5, 11.75  # statistics.quantiles, n=4
    assert stats.spread([9, 10, 11, 12]) == pytest.approx((q3 - q1) / q2)


def test_every_seed_gets_the_same_multiset_in_another_order():
    for traffic in ({"keys": "zipf", "zipf_a": 1.1},
                    {"keys": "uniform"}):
        a = workload.key_sequence(traffic, 5000, 20000, 1)
        b = workload.key_sequence(traffic, 5000, 20000, 2**31 + 7)
        assert not np.array_equal(a, b)
        assert np.array_equal(np.sort(a), np.sort(b))
        assert np.array_equal(
            a, workload.key_sequence(traffic, 5000, 20000, 1)
        )
    open_ = {"rate": 1000.0}
    d1 = workload.due_offsets_ns(open_, 4000, 3)
    d2 = workload.due_offsets_ns(open_, 4000, 4)
    assert not np.array_equal(d1, d2)
    assert np.allclose(np.sort(np.diff(d1, prepend=0)),
                       np.sort(np.diff(d2, prepend=0)), rtol=0, atol=2)
    assert abs(d1[-1] / 1e9 - 4.0) < 0.01  # 4000 requests at 1000/s


def test_zipf_counts_sum_and_skew():
    c = workload.key_counts({"keys": "zipf", "zipf_a": 1.1}, 1000, 50000)
    assert c.sum() == 50000
    assert (np.diff(c) <= 0).all()


def test_keys_round_trip_and_frames():
    idx = np.array([0, 7, 999_999])
    keys = workload.key_bytes(GW, idx)
    assert keys[1] == b"gw:0000007"
    assert workload.parse_keys(GW, keys + [b"other"]).tolist() == [
        0, 7, 999_999, -1]
    buf, off = workload.frames(GW, idx[:2])
    assert buf[: off[1]] == (
        b"*5\r\n$8\r\nTHROTTLE\r\n$10\r\ngw:0000000\r\n$1\r\n5\r\n"
        b"$2\r\n50\r\n$2\r\n30\r\n"
    )
    burst, count, period = workload.limits(GW, [7])
    assert (burst[0], count[0], period[0]) == (12, 57, 37)


def test_parse_replies_with_and_without_error_lines():
    good = b"*5\r\n:1\r\n:10\r\n:9\r\n:0\r\n:0\r\n*5\r\n:0\r\n:5\r\n:0\r\n:3\r\n:2\r\n"
    r, ok = workload.parse_replies(good, 2)
    assert r.tolist() == [[1, 10, 9, 0, 0], [0, 5, 0, 3, 2]]
    assert ok.all()
    mixed = b"-ERR overloaded\r\n" + good
    r, ok = workload.parse_replies(mixed, 3)
    assert ok.tolist() == [False, True, True]
    assert r[2].tolist() == [0, 5, 0, 3, 2]
    with pytest.raises(ValueError):
        workload.parse_replies(good[:-3], 2)


def test_reference_by_hand():
    """burst 3, 1 per second: three allowed at once, then denied."""
    t = 1_700_000_000 * 10**9
    out = gcra.decide([0, 0, 0, 0], [t] * 4, [1] * 4, [3], [1], [1],
                      [0], [0])
    assert out[:, 0].tolist() == [1, 1, 1, 0]
    assert out[:, 2].tolist() == [2, 1, 0, 0]     # remaining
    assert out[3].tolist() == [0, 3, 0, 4, 1]     # reset 4 s, retry 1 s
    # One second later one more is allowed.
    out = gcra.decide([0] * 5, [t] * 4 + [t + 10**9], [1] * 5, [3], [1],
                      [1], [0], [0])
    assert out[4, 0] == 1


def test_reference_agrees_with_the_program_oracle():
    """A second witness: the program's scalar oracle (core/) decides
    random streams as the reference does."""
    from throttlecrab_tpu.core.rate_limiter import RateLimiter
    from throttlecrab_tpu.core.store.periodic import PeriodicStore

    rng = np.random.default_rng(5)
    n_keys, n = 50, 3000
    burst = rng.integers(1, 20, n_keys)
    count = rng.integers(1, 500, n_keys)
    period = rng.integers(1, 100, n_keys)
    keys = rng.integers(0, n_keys, n)
    t0 = 1_700_000_000 * 10**9
    now = t0 + np.cumsum(rng.integers(0, 50_000_000, n))
    ref = gcra.decide(keys, now, np.ones(n, np.int64), burst, count,
                      period, np.zeros(n_keys, np.int64),
                      np.zeros(n_keys, np.int64))
    lim = RateLimiter(PeriodicStore())
    for i in range(n):
        k = int(keys[i])
        allowed, r = lim.rate_limit(f"k{k}", int(burst[k]), int(count[k]),
                                    int(period[k]), 1, int(now[i]))
        assert ref[i].tolist() == [int(allowed), r.limit, r.remaining,
                                   r.reset_after_secs, r.retry_after_secs]


def test_the_millisecond_control_differs():
    rng = np.random.default_rng(9)
    keys = rng.integers(0, 20, 5000)
    now = 1_700_000_000 * 10**9 + np.cumsum(rng.integers(0, 3_000_000, 5000))
    args = (keys, now, np.ones(5000, np.int64), [5] * 20, [97] * 20,
            [3] * 20, np.zeros(20, np.int64), np.zeros(20, np.int64))
    a = gcra.decide(*args)
    b = gcra.decide(*args, clock_ns=1_000_000)
    assert gcra.compare(keys, a, keys, b)[0] > 0


def test_compare_is_a_per_key_multiset_comparison():
    keys = np.array([1, 1, 2])
    r = np.array([[1, 5, 4, 0, 0], [1, 5, 3, 0, 0], [0, 5, 0, 1, 1]])
    assert gcra.compare(keys, r, keys[::-1], r[::-1]) == (0, 0)
    r2 = r.copy()
    r2[1, 2] = 4
    assert gcra.compare(keys, r2, keys, r) == (1, 1)
    assert gcra.compare(keys[:2], r[:2], keys, r) == (0, 1)


def test_interval_union():
    s = np.array([0, 5, 2, 20, 21])
    e = np.array([3, 8, 4, 25, 22])
    ms, me = trace_reduce._union(s, e)
    assert ms.tolist() == [0, 5, 20]
    assert me.tolist() == [4, 8, 25]


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, [_Ev(*e) for e in events]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, [_Line(*x) for x in lines]


class _Profile:
    """The shape `jax.profiler.ProfileData` has: planes, lines, events
    with a start and a duration in ns."""

    def __init__(self, planes):
        self.planes = [_Plane(*p) for p in planes]


def _synthetic():
    # Two launches of the decision program and one of another program
    # on one TPU; host threads overlapping the gaps between them.
    return _Profile([
        ("/device:TPU:0", [
            ("XLA Modules", [("jit_gcra_scan_packed_ins(12)", 1000, 400),
                             ("jit_gcra_scan_packed_ins(12)", 5000, 300),
                             ("jit_poll(3)", 9000, 100)]),
            ("XLA Ops", [("fusion.1", 1000, 250), ("scatter.2", 1200, 200),
                         ("fusion.1", 5000, 300), ("reduce.3", 9000, 100)]),
            ("Steps", [("0", 0, 10_000)]),
        ]),
        ("/host:CPU", [
            ("tk-native-redis", [("PjitFunction(gcra_scan_packed_ins)",
                                  900, 150),
                                 ("ws_next_batch", 1500, 3400)]),
            ("python", [("harness window", 0, 10_000)]),
        ]),
        ("/device:CPU:0", [("XLA Ops", [("copy", 0, 50_000)])]),
    ])


def test_trace_reduction_on_a_synthetic_trace():
    red = trace_reduce.reduce(_synthetic())
    assert red["devices"] == 1
    # Ops [1000, 1400) ∪ [5000, 5300) ∪ [9000, 9100): 400 + 300 + 100 ns.
    assert red["busy_s"] == pytest.approx(800e-9)
    assert red["modules"]["jit_gcra_scan_packed_ins"] == {
        "seconds": pytest.approx(700e-9), "count": 2}
    assert trace_reduce.module_seconds(red, (r"gcra_",)) == (
        pytest.approx(700e-9), 2)
    assert trace_reduce.module_seconds(red, (r"nothing",)) is None
    assert red["device_ops"][0] == ["jit_gcra_scan_packed_ins/fusion.1",
                                    pytest.approx(550e-9)]
    assert red["device_ops"][-1] == ["jit_poll/reduce.3",
                                     pytest.approx(100e-9)]
    # Gaps, longest first: [5300, 9000) then [1400, 5000); each named by
    # the shortest host event covering half of it or more.
    assert red["idle_gaps"] == [
        ["python: harness window", pytest.approx(3700e-9)],
        ["tk-native-redis: ws_next_batch", pytest.approx(3600e-9)],
    ]


def test_trace_reduction_without_a_device():
    red = trace_reduce.reduce(_Profile([("/host:CPU", [])]))
    assert red["busy_s"] is None and red["idle_gaps"] == []
    idle = spec.load_reader(ROOT / "benchmark/metrics/device.idle_share.py")
    ctx = type("Ctx", (), {"trace": red, "trace_window_s": 1.0})()
    assert idle.read(ctx) is None


def test_roofline_share_from_a_reduced_trace():
    roof = spec.load_reader(ROOT / "benchmark/metrics/gcra_roofline.py")

    class Ctx:
        trace = trace_reduce.reduce(_synthetic())
        device = {"kind": "TPU v5 lite"}

        def delta(self, name):
            return {"throttlecrab_tpu_batched_requests": 1000}[name]

        def peaks(self):
            return spec.peaks(self.device["kind"])

    share = roof.read(Ctx())
    # 56,000 B in 700 ns against 819 GB/s.
    assert share == pytest.approx(100 * 56_000 / 700e-9 / 819e9)


def test_a_new_cell_is_found_from_files_alone(tmp_path):
    """A cell, its configuration, its traffic mix and a metric added as
    files and entries, with no file of the benchmark edited."""
    bench = tmp_path / "benchmark"
    shutil.copytree(ROOT / "benchmark", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = dict(GW, name="gw-2k", keys={"count": 2000, "prefix": "gw:",
                                          "digits": 7})
    (bench / "configs" / "gw-2k.json").write_text(json.dumps(config))
    (bench / "traffic" / "burst.json").write_text(json.dumps(
        {"loop": "open", "keys": "zipf", "zipf_a": 0.9, "rate": 50,
         "connections": 2, "processes": 1, "warmup_s": 1,
         "warmup_settle_s": 1, "warmup_max_s": 5}))
    (bench / "metrics" / "replies.total.py").write_text(
        "def read(ctx):\n    return ctx.replies_in_window\n")
    doc["configs"].append({"name": "gw-2k", "source": "test",
                           "file": "benchmark/configs/gw-2k.json",
                           "reduced": ["keys"], "why": "test"})
    doc["workloads"].append({"name": "gw-2k.burst", "config": "gw-2k",
                             "traffic": "burst", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "replies.total", "unit": "replies",
                             "better": "higher", "source": "host_clock",
                             "layer": "test", "moves": "p50_ms",
                             "workloads": ["gw-2k.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    cell = spec.Cell(tmp_path, "gw-2k.burst", bench_dir=bench)
    assert cell.config["keys"]["count"] == 2000
    assert cell.traffic["rate"] == 50
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == ["replies.total"]
    assert "setup_s" in [m["name"] for m in cell.metrics("end_to_end")]
    ctx = type("Ctx", (), {"replies_in_window": 12})()
    assert cell.reader("replies.total").read(ctx) == 12
    keys, due = workload.stream(cell.traffic, cell.config, 2.0, 3)
    assert len(keys) == len(due) and keys.max() < 2000
    for p, data in before.items():
        assert p.read_bytes() == data
    # The repository's own cells still load.
    for w in doc["workloads"][:-1]:
        spec.Cell(ROOT, w["name"])


def test_a_split_metric_reads_its_stems_reader():
    cell = spec.Cell(ROOT, "gw-1m.zipf-sat")
    for name in ("device.idle_share.sat", "driver.decisions_per_launch.sat"):
        stem = name.rpartition(".")[0]
        assert cell.reader(name).__file__.endswith(f"/metrics/{stem}.py")
    with pytest.raises(spec.SpecError):
        cell.reader("no_such.metric")


# Requests of key 0 in flight over [10, 20] and [40, 50]; key 1 over
# [15, 30]; the last request of key 0 is never answered.
CLOCK_REQS = ([0, 1, 0, 0], [10, 15, 40, 60], [20, 30, 50, 2**61])


@pytest.mark.parametrize("key, now, slack, outside", [
    (0, 12, 0, 0),    # inside the first request's flight
    (0, 30, 0, 1),    # between key 0's flights
    (0, 30, 10, 0),   # ... within the slack of one
    (1, 14, 0, 1),    # before key 1's request went out
    (0, 70, 0, 0),    # the unanswered request is still in flight
    (2, 20, 0, 1),    # a key no client asked for
])
def test_clock_readings_outside_every_flight(key, now, slack, outside):
    assert clock.outside(*CLOCK_REQS, [key], [now], slack) == outside


def test_clock_offset_is_read_closely():
    off, err = clock.offset_ns()
    assert 0 < err < 50_000_000
    assert abs(time.time_ns() - time.monotonic_ns() - off) < 50_000_000


V5E_TRACE = Path(__file__).with_name("fixtures") / "v5e_zipf_sat_1s.xplane.pb.gz"


def test_trace_reduction_on_a_v5e_trace():
    """A 1 s traced window of gw-1m.zipf-sat on one TPU v5e (my chip
    run, PR 22): the device plane, its programs and the gaps' names."""
    red = trace_reduce.reduce(trace_reduce.load(V5E_TRACE))
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(0.017294894)
    assert red["modules"]["jit_gcra_scan_packed_ins"]["count"] == 5
    assert trace_reduce.module_seconds(red, (r"gcra_",)) == (
        pytest.approx(0.015770573), 5)
    assert red["device_ops"][0] == ["jit_gcra_scan_packed_ins/%while.5",
                                    pytest.approx(0.015726657)]
    assert len(red["idle_gaps"]) == 10
    assert red["idle_gaps"][0] == ["python3: np.asarray(jax.Array)",
                                   pytest.approx(0.21941564)]
    # Ops nest (a scan's loop holds its body's ops), so only each one
    # alone is bounded by the busy time.
    assert all(t <= red["busy_s"] for _, t in red["device_ops"])


MESH = json.loads((ROOT / "benchmark/configs/tenants-mesh4.json").read_text())


@pytest.mark.parametrize("name, digest, first, last", [
    ("gw-1m", "b3a54240099537d94b4b1ce4f48d14eb"
              "9f7908c1d7f70a06de4db8b55bc78b19", b"gw:0000000", b"gw:0999999"),
    ("api-10k", "52f7cc25280311d51baee3eb02e8a807"
                "572e8cc5b025b54fdd24d3aaadfbdd26", b"api:00000", b"api:09999"),
])
def test_keys_without_tenants_keep_their_bytes(name, digest, first, last):
    """Every key of the configurations without tenants, byte for byte
    as the benchmark made them before tenant keys existed."""
    import hashlib

    config = json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())
    keys = workload.key_bytes(config, range(workload.n_keys(config)))
    assert (keys[0], keys[-1]) == (first, last)
    assert hashlib.sha256(b"".join(keys)).hexdigest() == digest
    assert workload.n_tenants(config) == 0


def test_tenant_keys_round_trip():
    n = workload.n_keys(MESH)
    assert n == 64 * 100_000
    idx = np.array([0, 1, 63, 64, 7 + 64 * 12345, n - 1])
    keys = workload.key_bytes(MESH, idx)
    assert keys[:4] == [b"t00:00000", b"t01:00000", b"t63:00000",
                        b"t00:00001"]
    assert keys[4] == b"t07:12345" and keys[5] == b"t63:99999"
    assert workload.tenant_of(MESH, idx).tolist() == [0, 1, 63, 0, 7, 63]
    names = workload.tenant_names(MESH)
    assert len(names) == 64 and names[7] == "t07"
    # Each key's namespace (the text before ':') is its tenant's name.
    assert all(k.split(b":")[0].decode() == names[t] for k, t in
               zip(keys, workload.tenant_of(MESH, idx)))
    others = [b"t64:00000", b"t07-12345", b"u07:12345", b"t07:1234x",
              b"t7:012345", b"gw:0000007"]
    assert workload.parse_keys(MESH, keys + others).tolist() == (
        idx.tolist() + [-1] * len(others))


def test_tenant_count_gap_folds_the_overflow_bucket():
    from benchmark.harness import tenant_count_gap, tenant_totals

    config = {"keys": {"count": 12, "prefix": "t", "digits": 2,
                       "tenants": 3}}
    keys = np.array([0, 1, 2, 3, 4, 5, 0, -1])
    allowed = np.array([1, 1, 0, 1, 0, 0, 0, 1])
    ref = tenant_totals(config, keys, allowed)
    assert ref.tolist() == [[2, 1], [1, 1], [0, 2]]  # the -1 key left out
    before = {"t00": [5, 5]}
    # t02 is counted in the overflow bucket; t00 grew by its reference.
    after = {"t00": [7, 6], "t01": [1, 1], "~overflow": [0, 2]}
    assert tenant_count_gap(config, before, after, ref) == 0
    shifted = dict(after, t01=[2, 1])
    assert tenant_count_gap(config, before, shifted, ref) == 1
    lost = dict(after, **{"~overflow": [0, 1]})
    assert tenant_count_gap(config, before, lost, ref) == 1
    assert tenant_count_gap(config, {}, {}, ref) == 7


def test_sharded_launch_shapes_cover_every_window():
    from benchmark.harness import sharded_launch_shapes

    config = type("C", (), {"batch_size": 4096, "max_scan_depth": 16})()
    shapes = sharded_launch_shapes(config, 4, 16)
    single = [b for k, b in shapes if k == 1]
    assert single == [16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
    # A scan follows a full first batch: its widest shard holds at least
    # a quarter of 4096 rows.
    for k in (2, 4, 8, 16):
        assert [b for kk, b in shapes if kk == k] == [1024, 2048, 4096]
    assert len(shapes) == 21
    one = type("C", (), {"batch_size": 100, "max_scan_depth": 1})()
    assert sharded_launch_shapes(one, 4, 16) == [
        (1, 16), (1, 32), (1, 64), (1, 128)]


def _mesh_trace():
    # Two chips: each runs one scan launch holding a while loop and an
    # all-reduce of its counters; chip 1's all-reduce overlaps an
    # all-gather for 10 ns.
    def chip(n, ar_start):
        return (f"/device:TPU:{n}", [
            ("XLA Modules", [("jit_local(4)", 1000, 400)]),
            ("XLA Ops", [("%while.2 = (s32[]) while(...)", 1000, 300),
                         ("%all-reduce.1 = s64[3] all-reduce(...)",
                          ar_start, 40),
                         ("%all-gather.7 = s64[2,3] all-gather(...)",
                          ar_start + 30, 20)]),
        ])
    return _Profile([chip(0, 1300), chip(1, 1350)])


def test_collective_share_on_a_small_trace():
    red = trace_reduce.reduce(_mesh_trace())
    assert red["devices"] == 2
    # Chip 0: [1000, 1350) busy, collectives [1300, 1350): 350 and 50 ns.
    # Chip 1: [1000, 1300) and [1350, 1400): 350 busy, 50 collective.
    assert red["busy_s"] == pytest.approx(350e-9)
    share = spec.load_reader(
        ROOT / "benchmark/metrics/mesh.collective_share.py")
    assert trace_reduce.op_seconds(
        red, share.COLLECTIVES) == pytest.approx(50e-9)
    assert trace_reduce.op_seconds(red, (r"nothing",)) is None
    ctx = type("Ctx", (), {"trace": red})()
    assert share.read(ctx) == pytest.approx(100 * 50 / 350)
    # One chip (or none): no mesh to read.
    ctx.trace = trace_reduce.reduce(_synthetic())
    assert share.read(ctx) is None
