"""Every metric BENCHMARK.json names has a reader, and the readers turn a
run's readings into the numbers their docstrings promise."""
import pytest

from chipbench import cells, harness

READINGS = {
    "setup_s": 50.0, "window_s": 40.0, "tokens": 8000,
    "engine_steps": 1000, "instances": 1,
    "row_slots_active": 6000, "row_slots_total": 8000,
    "drafted": 400, "accepted": 100, "chunks": 250, "migrations": 40,
    "latencies": [float(i) for i in range(1, 101)],
    "iterations": [{"makespan": 20.0, "finish": [1.0] * 9 + [20.0]},
                   {"makespan": 20.0, "finish": [2.0] * 10}],
    "model_flops": 197e12 * 0.4, "peak_flops": 197e12,
    "memory_peak_bytes": 8e9, "hbm_bytes": 16e9,
    "trace": {"busy_s": 10.0, "window_s": 40.0},
}
WANT = {
    "rollout_tokens_per_s": 200.0, "setup_s": 50.0, "step_ms": 40.0,
    "row_occupancy": 75.0, "tokens_per_row_step": 8000 / 6000,
    "spec_accept_rate": 25.0, "device_ms_per_step": 10.0,
    "device_idle_share": 75.0, "step_mfu": 1.0, "peak_hbm_share": 50.0,
    # iteration 1: 90% (9 of 10) done at 1.0 s of 20 s; iteration 2 at 2.0
    "tail_share": 100.0 * (19.0 + 18.0) / 40.0,
    "request_p90_s": 90.1,
    "migration_share": 16.0,
}


def all_metrics():
    b = cells.benchmark()
    return [m["name"] for m in b["end_to_end"] + b["per_layer"]]


@pytest.mark.parametrize("name", all_metrics())
def test_reader(name):
    assert harness.load_reader(name)(READINGS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", ["device_ms_per_step", "device_idle_share"])
def test_trace_readers_read_nothing_without_a_trace(name):
    assert harness.load_reader(name)(dict(READINGS, trace=None)) is None


@pytest.mark.parametrize("name,want", [("step_ms", 160.0),
                                       ("device_ms_per_step", 40.0)])
def test_step_readers_count_the_steps_of_one_instance(name, want):
    # four instances step side by side: 1000 steps together are 250 each
    assert harness.load_reader(name)(dict(READINGS, instances=4)) == \
        pytest.approx(want)


def test_migration_share_reads_nothing_without_chunks():
    r = dict(READINGS, chunks=0, migrations=0)
    assert harness.load_reader("migration_share")(r) is None
