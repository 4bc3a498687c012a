"""Trace reduction, on hand-made events and on a trace recorded here."""
import jax
import jax.numpy as jnp
import pytest

from chipbench import trace_reduce as tr


def test_union_gaps_and_busy():
    cover = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.7)])
    assert cover == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.gaps(cover, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.busy_seconds(cover, 1.0, 3.5) == pytest.approx(1.5)


def test_reduce_events_averages_chips_and_names_gaps():
    device = {"/device:TPU:0": [("fusion", 0.0, 1.0), ("dot", 2.0, 3.0)],
              "/device:TPU:1": [("fusion", 0.0, 3.0)]}
    host = [("wait", 0.9, 2.1), ("schedule", 1.0, 1.5),
            ("outer", -5.0, 10.0)]
    out = tr.reduce_events(device, host, 0.0, 4.0)
    assert out["busy_s"] == pytest.approx((2.0 + 3.0) / 2)
    assert out["window_s"] == 4.0
    assert out["device_ops"][0] == ["fusion", 4.0]
    # the 1 s gaps of chip 0 ((1, 2) and (3, 4)) and chip 1 ((3, 4)):
    # the first is covered most by "wait" (its whole length), the later
    # ones only by the outer event
    names = dict((round(d, 6), n) for n, d in out["idle_gaps"])
    assert len(out["idle_gaps"]) == 3
    assert out["idle_gaps"][0][0] in ("wait", "outer")
    assert "wait" in [n for n, _ in out["idle_gaps"]]
    assert names[1.0] in ("wait", "outer")


def test_no_device_events_read_as_nothing():
    assert tr.reduce_events({}, [("x", 0, 1)], 0.0, 1.0) is None
    assert tr.reduce_events({"/device:TPU:0": []}, [], 0.0, 1.0) is None


def test_a_recorded_trace_is_read(tmp_path):
    # on the CPU there is no TPU plane: the window annotation is found on
    # the host plane and the reduction reports nothing, never a zero
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW_EVENT):
            jnp.ones((64, 64)).sum().block_until_ready()
    found = sorted(tmp_path.rglob("*.xplane.pb"))
    assert found
    device, host, lo, hi = tr.read_xspace(str(found[-1]))
    assert hi > lo
    assert all(n != tr.WINDOW_EVENT for n, _, _ in host)
    assert tr.reduce_xspace(str(found[-1])) is None
