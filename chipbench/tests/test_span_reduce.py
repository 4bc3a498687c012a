"""Idle attribution: on hand-made spans, each idle instant goes to the
innermost span covering it and the charges partition the idle time; on
a trace recorded here, the phase spans and program names are read; and a
traced run of the tiny cell is served as an untraced one is."""
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import span_reduce as sr
from chipbench import trace_reduce as tr
from chipbench.attribute_idle import attribute
from chipbench.tests import tiny


def test_innermost_tiles_the_window():
    spans = [("seer.tick", 0.0, 10.0), ("seer.commit", 6.0, 9.0),
             ("seer.commit_wait", 6.5, 8.0), ("seer.dispatch", 1.0, 3.0),
             ("seer.import", 1.5, 2.0), ("seer.admit", 12.0, 13.0)]
    pieces = sr.innermost(spans, -1.0, 14.0)
    assert pieces == [
        (-1.0, 0.0, None), (0.0, 1.0, "seer.tick"),
        (1.0, 1.5, "seer.dispatch"), (1.5, 2.0, "seer.import"),
        (2.0, 3.0, "seer.dispatch"), (3.0, 6.0, "seer.tick"),
        (6.0, 6.5, "seer.commit"), (6.5, 8.0, "seer.commit_wait"),
        (8.0, 9.0, "seer.commit"), (9.0, 10.0, "seer.tick"),
        (10.0, 12.0, None), (12.0, 13.0, "seer.admit"), (13.0, 14.0, None)]


def test_idle_goes_to_the_innermost_span_and_partitions():
    host = [("seer.tick", 0.0, 10.0), ("seer.dispatch", 1.0, 3.0),
            ("seer.import", 1.5, 2.0), ("seer.commit", 6.0, 9.0),
            ("seer.commit_wait", 6.5, 8.0), ("seer.iteration_close",
                                             10.0, 10.5),
            ("python frame", -5.0, 20.0), (sr.LAUNCH, 1.2, 1.3)]
    # busy [2.5, 7.0) on chip 0 and [1.8, 7.5) on chip 1
    device = {"/device:TPU:0": [("%fusion.1", 2.5, 7.0)],
              "/device:TPU:1": [("%fusion.1", 1.8, 4.0),
                                ("%fusion.2", 4.0, 7.5)]}
    modules = {"/device:TPU:0": [("jit_seer_step_t8(1)", 2.5, 7.0)],
               "/device:TPU:1": [("jit_seer_step_t8(1)", 1.8, 7.5)]}
    scope_of = {"%fusion.1": "jit(seer_step_t8)/while/body/attention/dot:",
                "%fusion.2": "jit(seer_step_t8)/while/body/mlp/dot:"}
    red = sr.reduce_spans(device, modules, host, 0.0, 12.0, scope_of)
    idle = red["idle_s"]
    # chip 0 idle: [0, 2.5) and [7, 12); chip 1: [0, 1.8) and [7.5, 12)
    want = {"seer.tick": (2.0 + 2.0) / 2,
            "seer.dispatch": (1.0 + 0.5) / 2,
            "seer.import": (0.5 + 0.3) / 2,
            "seer.commit_wait": (1.0 + 0.5) / 2,
            "seer.commit": (1.0 + 1.0) / 2,
            "seer.iteration_close": 0.5,
            "unattributed": 1.5}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v), k
    chip_idle = [tr.gaps(tr.union((s, e) for _, s, e in ops), 0.0, 12.0)
                 for ops in device.values()]
    total = sum(e - s for g in chip_idle for s, e in g) / 2
    assert red["idle_total_s"] == pytest.approx(total)
    assert sum(idle.values()) == pytest.approx(total)
    assert red["program_busy_s"] == pytest.approx({"seer_step_t8": 5.1})
    assert red["attention_busy_s"] == pytest.approx((4.5 + 2.2) / 2)
    assert red["step_busy_s"] == pytest.approx(5.1)
    # the host's self time: the pieces of each phase in the window
    assert red["host_self_s"]["seer.dispatch"] == pytest.approx(1.5)
    assert red["host_self_s"]["seer.tick"] == pytest.approx(5.0)
    assert "python frame" not in red["host_self_s"]
    # the one step ended at 7.0 / 7.5 and its wait ended at 8.0; its
    # launch inside the dispatch began at 1.2, the run at 2.5 / 1.8
    assert red["device_lead_s"] == pytest.approx((1.2 - 1.8, 0.5))
    no_launch = [h for h in host if h[0] != sr.LAUNCH]
    assert sr.device_lead(modules, no_launch) == pytest.approx(
        (1.0 - 1.8, 0.5))

    step = sr.per_step(red, 2)
    groups = list(sr.GROUPS) + ["unattributed"]
    assert sum(step[f"{g}_idle_ms"] for g in groups) == \
        pytest.approx(step["idle_ms"])
    assert step["dispatch_idle_ms"] == pytest.approx(1e3 * 0.75 / 2)
    assert step["migration_idle_ms"] == pytest.approx(1e3 * 0.4 / 2)
    assert step["commit_idle_ms"] == pytest.approx(
        1e3 * (2.0 + 0.75 + 1.0) / 2)
    assert step["attention_busy_share"] == pytest.approx(
        100 * 3.35 / 5.1)


def test_no_phase_spans_read_as_nothing():
    device = {"/device:TPU:0": [("%fusion.1", 0.0, 1.0)]}
    assert sr.reduce_spans(device, {}, [("_stream_loop", 0.0, 2.0)],
                           0.0, 2.0, {}) is None
    assert sr.reduce_spans({}, {}, [("seer.tick", 0.0, 2.0)],
                           0.0, 2.0, {}) is None


def test_program_and_scope_names():
    assert sr.program("jit_seer_step_t64(9792075815746685405)") == \
        "seer_step_t64"
    assert sr.program("jit_seer_export(1)") == "seer_export"
    path = "jit(seer_step_t4)/while/body/closed_call/attention/tanh:"
    assert sr.in_scope(path, "attention")
    assert not sr.in_scope(path, "mlp")
    assert not sr.in_scope("jit(attention)/add:", "attention")


def test_a_recorded_trace_is_read(tmp_path):
    """The phase spans of a Tracer, the window and the module names are
    where the reduction looks for them (on the CPU there is no device
    plane: the reduction reads as nothing)."""
    from repro.obs import Tracer
    t = Tracer()

    @jax.jit
    def seer_step_t4(x):
        with jax.named_scope("attention"):
            return jnp.tanh(x @ x).sum()

    x = jnp.ones((64, 64))
    seer_step_t4(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW_EVENT):
            t.begin_tick(0)
            with t.phase("seer.dispatch", "inst0"):
                y = seer_step_t4(x)
            with t.phase("seer.commit_wait", "inst0"):
                y.block_until_ready()
            t.end_tick()
            time.sleep(0.001)
    path = str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])
    device, host, lo, hi = tr.read_xspace(path)
    names = [n for n, _, _ in host if n.startswith("seer.")]
    assert sorted(names) == ["seer.commit_wait", "seer.dispatch",
                             "seer.tick"]
    assert all(lo <= s <= e <= hi for n, s, e in host
               if n.startswith("seer."))
    with open(path, "rb") as f:
        assert sr.op_scopes(f.read()) == {}
    assert sr.read_modules(path) == {}
    assert sr.reduce_file(path) is None


def test_a_traced_tiny_run_is_served_as_an_untraced_one():
    """The tracer attaches for the window only and changes nothing the
    check reads; every engine step of the window has its dispatch and
    its commit wait."""
    out, tracer = attribute(tiny.cell(), 2 ** 31 + 21, 0.5,
                            time.monotonic(), require_chip=False,
                            log=lambda m: None)
    line = out["result"]
    assert line["correct"], line["checks"]
    assert out["attribution"] is None          # no device plane here
    evs = [e for e in tracer.events() if e["cat"] == "phase"]
    waits = [e for e in evs if e["name"] == "seer.commit_wait"]
    dispatches = [e for e in evs if e["name"] == "seer.dispatch"]
    assert waits and len(dispatches) >= len(waits)
    assert {"seer.iteration_open", "seer.admit", "seer.commit",
            "seer.export", "seer.import"} <= {e["name"] for e in evs}
