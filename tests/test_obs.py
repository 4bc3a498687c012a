"""Flight-recorder observability: tracing must be pure observation.

Tracer level: ticks and the wall clock shared with the JAX profiler
(phase spans land in a profiler trace under their own names, at the
Tracer's stamps), Chrome trace-event round-trip, and the cross-tier
event schema.

Timeline level: span-conservation on synthetic timelines (gaps and
short sums are *detected*, not papered over) and the tail-attribution
report's shape.

Rollout level: a traced run is bit-identical to an untraced one
(tokens, engine steps, host syncs), an untraced run creates no profiler
annotation, the trace's ticks, names and args are a pure function of
(seed, config), every finished request's phase spans tile its wall
interval in ticks and wall seconds, the tick's host phases nest as the
idle attribution reads them, and a crash schedule shows up as
``recovery`` spans with the recovery-path kind stamped on the instant —
all without tripping the device->host transfer guard.

Engine level: the fused step is named ``seer_step_t{T}`` in HLO with
its model parts in named scopes, and the column counters count the
step's mask.

Stats level: the ``RolloutStats`` counter audit, mechanized — every
field documented and read somewhere outside its definition — and the
unified ``snapshot()`` surface benches consume."""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core.faults import FaultEvent, FaultInjector
from repro.core.request import make_groups
from repro.core.rollout import RolloutStats, SeerRollout
from repro.engine import EngineSeq, Instance, StepFunctions
from repro.obs import (PHASES, RequestTimeline, Tracer, format_attribution,
                       tail_attribution, timelines_from_events)
from repro.obs.trace import CATEGORIES, SCHEMA_KEYS, TICK_EVENT, schema_keys


@pytest.fixture(scope="module")
def tiny(tiny_params_cache):
    cfg, params = tiny_params_cache("granite-3-8b")
    return cfg, params, StepFunctions(cfg)


def _prompts(cfg, n_groups=3):
    return [[(7 * g + 3 * j) % (cfg.vocab_size - 2) + 1
             for j in range(6 + 4 * g)]
            for g in range(n_groups)]


def _rollout(cfg, params, steps, injector=None, **kw):
    defaults = dict(n_instances=2, max_slots=2, cache_len=64,
                    chunk_size=5, prefill_chunk=8, policy="seer",
                    spec_decode=False, gamma_max=8, base_seed=7,
                    watchdog_ticks=3, fetch_retries=3,
                    fault_injector=injector, steps=steps)
    defaults.update(kw)
    return SeerRollout(cfg, params, **defaults)


def _run(cfg, params, steps, tracer=None, injector=None, max_new=12, **kw):
    ro = _rollout(cfg, params, steps, injector, tracer=tracer, **kw)
    hs0 = steps.host_syncs
    st0 = sum(i.steps_run for i in ro.instances)
    res = ro.run(make_groups(_prompts(cfg), group_size=2,
                             max_new_tokens=max_new, seed=5))
    return (res, sum(i.steps_run for i in ro.instances) - st0,
            steps.host_syncs - hs0)


@pytest.fixture(scope="module")
def traced_run(tiny):
    """One traced + one untraced run of the same seeded workload,
    shared across the bit-identity / determinism / conservation tests."""
    cfg, params, steps = tiny
    res_off, steps_off, syncs_off = _run(cfg, params, steps)
    tr = Tracer()
    res_on, steps_on, syncs_on = _run(cfg, params, steps, tracer=tr)
    return {"off": (res_off, steps_off, syncs_off),
            "on": (res_on, steps_on, syncs_on), "tracer": tr}


# ---------------- tracer primitives ------------------------------------------


def test_tracer_clock_and_event_resolution():
    tr = Tracer()
    tr.begin_tick(0)
    tr.instant("a", "instance", "inst0", x=1)
    tr.end_tick()
    tr.begin_tick(1)
    with tr.phase("seer.admit", "scheduler", n=2) as ev:
        ev.args["admitted"] = 1
    tr.end_tick()
    tr.span("decode", "request", "r0", 0, 2)
    tr.span("sim", "request", "r1", 0, 1, t0=3.0, t1=4.5)
    t = [tr.tick_time(k) for k in range(3)]
    assert 0.0 <= t[0] <= t[1] <= t[2] <= tr.now()
    assert tr.tick_time(99) == t[2]          # clamped, never IndexError
    evs = tr.events()
    assert [sorted(e) for e in evs] == [sorted(SCHEMA_KEYS)] * 4
    assert t[0] <= evs[0]["t0"] <= t[1] and evs[0]["args"] == {"x": 1}
    assert evs[1]["name"] == "seer.admit" and evs[1]["cat"] == "phase"
    assert evs[1]["tick0"] == evs[1]["tick1"] == 1
    assert t[1] <= evs[1]["t0"] <= evs[1]["t1"] <= t[2]
    assert evs[1]["args"] == {"n": 2, "admitted": 1}
    assert evs[2]["t0"] == t[0] and evs[2]["t1"] == t[2]  # tick table
    assert evs[3]["t0"] == 3.0 and evs[3]["t1"] == 4.5    # explicit floats
    assert all(e["cat"] in CATEGORIES for e in evs)
    tr.begin_tick(0)                         # a new stream's table
    assert tr.tick_time(1) == tr.tick_time(0) > t[2]
    tr.end_tick()


def _profiled(tmp_path, body):
    """Run ``body`` under a CPU profiler trace; its host-plane events as
    (name, absolute start ns, duration ns, stats)."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        body()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    planes = {p.name: p for p in pd.planes}
    # event starts are offsets from the session's start on the
    # profiler's host clock (CLOCK_REALTIME)
    base = dict(planes["Task Environment"].stats)["profile_start_time"]
    return [(ev.name, base + ev.start_ns, ev.duration_ns, dict(ev.stats))
            for line in planes["/host:CPU"].lines for ev in line.events]


def test_phase_spans_land_in_the_profiler_trace(tmp_path):
    """Each phase span and tick is a profiler annotation of the same
    name, and the Tracer's stamps are the profiler's: every span starts
    within 1 ms of its profiler event, with one clock and no offset."""
    tr = Tracer()

    def body():
        for k in range(3):
            tr.begin_tick(k)
            with tr.phase("seer.drafts", "inst0"):
                time.sleep(0.002)
            with tr.phase("seer.commit", "inst0"):
                with tr.phase("seer.commit_wait", "inst0"):
                    jnp.ones((32, 32)).sum().block_until_ready()
            tr.end_tick()

    host = _profiled(tmp_path, body)
    mine = [e for e in tr.events() if e["cat"] == "phase"]
    assert len(mine) == 9
    for name in ("seer.drafts", "seer.commit", "seer.commit_wait"):
        theirs = sorted(s for n, s, _, _ in host if n == name)
        ours = [tr.origin_ns + e["t0"] * 1e9 for e in mine
                if e["name"] == name]
        assert len(theirs) == len(ours) == 3
        for a, b in zip(ours, theirs):
            assert abs(a - b) < 1e6, (name, a - b)
    ticks = [st for n, _, _, st in host if n == TICK_EVENT]
    assert sorted(st["step_num"] for st in ticks) == [0, 1, 2]


def test_untraced_run_creates_no_annotation(tiny, monkeypatch):
    """With no tracer attached no span object and no profiler
    annotation is created: a run with both annotation types made to
    raise serves the same tokens as one without."""
    cfg, params, steps = tiny
    res_ref, _, _ = _run(cfg, params, steps)

    def refuse(*a, **k):
        raise AssertionError("annotation created with no tracer")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
    res, _, _ = _run(cfg, params, steps)
    assert res.responses() == res_ref.responses()
    with pytest.raises(AssertionError):
        _run(cfg, params, steps, tracer=Tracer())


def test_chrome_roundtrip_is_lossless():
    tr = Tracer()
    tr.begin_tick(0)
    tr.instant("fault_crash", "fault", "inst1", lose_pool=True, count=1)
    tr.end_tick()
    tr.span("queue", "request", "r0", 0, 1, tenant="a", group="g0")
    evs = tr.events()
    doc = json.loads(json.dumps(tr.to_chrome()))   # through real JSON
    assert Tracer.from_chrome(doc) == evs
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert names == {"thread_name"}                # per-track metadata


# ---------------- timeline + attribution (synthetic) -------------------------


def _tl(rid, spans, tenant="-", finished=True):
    tl = RequestTimeline(req_id=rid, tenant=tenant, finished=finished)
    tl.spans_s = [(ph, t0, t1) for ph, t0, t1 in spans]
    tl.segments = [(ph, int(t0), int(t1)) for ph, t0, t1 in spans]
    if spans:
        tl.submit_tick = int(spans[0][1])
        tl.end_tick = int(spans[-1][2])
    return tl


def test_conservation_detects_gaps_and_shortfalls():
    ok = _tl("r0", [("queue", 0.0, 1.0), ("decode", 1.0, 4.0)])
    assert ok.conserved()
    assert ok.phase_seconds() == {"queue": 1.0, "decode": 3.0}
    gap = _tl("r1", [("queue", 0.0, 1.0), ("decode", 2.0, 4.0)])
    assert not gap.conserved()
    empty = _tl("r2", [], finished=True)
    assert not empty.conserved()               # finished but no spans


def test_tail_attribution_report_shape():
    tls = {}
    for i in range(20):
        wall = 1.0 + i                         # r19 is the tail
        tls[f"r{i}"] = _tl(f"r{i}", [("queue", 0.0, 0.5),
                                     ("decode", 0.5, wall)],
                           tenant="a" if i % 2 else "b")
    shed = RequestTimeline(req_id="r_shed", shed=True)
    tls["r_shed"] = shed
    rep = tail_attribution(tls)
    assert rep["requests"] == 20 and rep["shed"] == 1
    assert rep["conserved"]
    assert rep["wall_s"]["p50"] <= rep["wall_s"]["p99"] \
        <= rep["wall_s"]["max"] == 20.0
    assert rep["cohorts"]["p99"]["n"] >= 1
    assert rep["cohorts"]["tail10"]["n"] >= rep["cohorts"]["p99"]["n"]
    decode_frac = rep["cohorts"]["p99"]["phases"]["decode"]["frac"]
    assert decode_frac > 0.9                   # the tail IS decode
    assert set(rep["per_tenant"]) == {"a", "b"}
    text = format_attribution(rep)
    assert "requests=20 shed=1" in text and "decode" in text


# ---------------- rollout: tracing is pure observation -----------------------


def test_trace_off_bit_identity(traced_run):
    """Attaching a tracer must not change tokens, engine steps or the
    host-sync count — the absence-of-the-feature gate."""
    res_off, steps_off, syncs_off = traced_run["off"]
    res_on, steps_on, syncs_on = traced_run["on"]
    assert res_on.responses() == res_off.responses()
    assert steps_on == steps_off
    assert syncs_on == syncs_off


def _untimed(events):
    """What of a trace is a pure function of (seed, config): everything
    but the engine tier's wall seconds."""
    return [{k: v for k, v in e.items() if k not in ("t0", "t1")}
            for e in events]


def test_trace_is_deterministic(tiny, traced_run):
    cfg, params, steps = tiny
    tr2 = Tracer()
    _run(cfg, params, steps, tracer=tr2)
    assert _untimed(tr2.events()) == _untimed(traced_run["tracer"].events())


def test_engine_chrome_roundtrip(traced_run):
    tr = traced_run["tracer"]
    doc = json.loads(json.dumps(tr.to_chrome()))
    assert Tracer.from_chrome(doc) == tr.events()


def test_span_conservation_on_engine_trace(traced_run):
    """Every finished request's phase spans tile its wall interval —
    exactly in ticks, and to fp tolerance in wall seconds."""
    evs = traced_run["tracer"].events()
    tls = timelines_from_events(evs)
    done = [tl for tl in tls.values() if tl.finished]
    assert len(done) == 6                      # 3 groups x group_size 2
    for tl in done:
        assert tl.conserved(), tl.req_id
        assert sum(b - a for _, a, b in tl.segments) == tl.wall_ticks
        assert {ph for ph, _, _ in tl.segments} <= set(PHASES)
    rep = tail_attribution(tls)
    assert rep["conserved"] and rep["requests"] == 6
    assert rep["phase_totals_s"].get("decode", 0.0) > 0.0


def test_engine_phases_nest_as_the_attribution_reads_them(traced_run):
    """The tick's host phases: each opens and closes within one tick,
    on the wall clock; every commit wait lies inside a commit of its
    instance, and no two phases of one tick overlap otherwise."""
    evs = [e for e in traced_run["tracer"].events() if e["cat"] == "phase"]
    names = {e["name"] for e in evs}
    assert {"seer.iteration_open", "seer.iteration_close", "seer.drafts",
            "seer.dispatch", "seer.admit", "seer.export", "seer.import",
            "seer.commit", "seer.commit_wait",
            "seer.cst_update"} >= names >= {
        "seer.iteration_open", "seer.iteration_close", "seer.dispatch",
        "seer.admit", "seer.commit", "seer.commit_wait",
        "seer.cst_update"}
    assert all(e["t0"] <= e["t1"] for e in evs)
    commits = [e for e in evs if e["name"] == "seer.commit"]
    waits = [e for e in evs if e["name"] == "seer.commit_wait"]
    assert len(waits) == len(commits) == traced_run["on"][1]
    for w in waits:
        assert any(c["track"] == w["track"] and c["t0"] <= w["t0"]
                   and w["t1"] <= c["t1"] for c in commits)
    nested = {"seer.commit_wait", "seer.cst_update", "seer.import"}
    top = sorted((e["t0"], e["t1"]) for e in evs
                 if e["name"] not in nested
                 and not e["name"].startswith("seer.iteration"))
    assert all(a[1] <= b[0] for a, b in zip(top, top[1:]))


def test_engine_schema_is_the_shared_schema(traced_run):
    evs = traced_run["tracer"].events()
    assert schema_keys(evs) == sorted(SCHEMA_KEYS)
    assert {e["cat"] for e in evs} <= set(CATEGORIES)


def test_tracer_hooks_pass_transfer_guard(tiny):
    """The dispatch and commit-wait spans record host ints already in
    hand; with the guard disallowing implicit device->host transfers, a
    traced step loop must behave exactly like the untraced one."""
    cfg, params, steps = tiny
    inst = Instance(cfg, params, steps, max_slots=2, cache_len=64,
                    gamma_max=0, prefill_chunk=8, base_seed=7)
    inst.tracer = Tracer()
    s = EngineSeq("r0", "g0", [2, 3, 4, 5, 6, 7], seed=3, max_new_tokens=8)
    inst.admit(s)
    inst.run_step()                            # warm compile outside guard
    while not s.finished:
        syncs0 = steps.host_syncs
        with jax.transfer_guard_device_to_host("disallow"):
            inst.run_step()
        assert steps.host_syncs - syncs0 <= 1
    assert len(s.generated) == 8
    names = {e["name"] for e in inst.tracer.events()}
    assert names == {"seer.dispatch", "seer.commit_wait"}


def test_column_counters_count_the_step_mask(tiny):
    """``cols_active`` is the sum of each step's mask, ``cols_total``
    max_slots x T: a 6-token prompt prefills its first 5 tokens at T=8
    with the pending 6th fused (6 of 16 columns), then decodes at T=1
    (1 of 2)."""
    cfg, params, steps = tiny
    inst = Instance(cfg, params, steps, max_slots=2, cache_len=64,
                    gamma_max=0, prefill_chunk=8, base_seed=7)
    s = EngineSeq("r0", "g0", [2, 3, 4, 5, 6, 7], seed=3, max_new_tokens=4)
    inst.admit(s)
    inst.run_step()
    assert (inst.cols_active, inst.cols_total) == (6, 16)
    inst.run_step()
    assert (inst.cols_active, inst.cols_total) == (7, 18)
    assert inst.row_slots_active == 2 and inst.row_slots_total == 4


def test_fused_step_is_a_named_program_with_scoped_parts(tiny):
    """The fused step lowers to a module named ``seer_step_t{T}`` whose
    HLO metadata puts the model's attention and MLP, sampling and
    acceptance under their named scopes."""
    from repro.engine.sampling import position_keys
    cfg, params, steps = tiny
    inst = Instance(cfg, params, steps, max_slots=2, cache_len=64,
                    gamma_max=0, prefill_chunk=8, base_seed=7)
    B, T = 2, 4
    z = jnp.zeros((B, T), jnp.int32)
    zb = jnp.zeros((B,), jnp.int32)
    lowered = steps.fused_step(T).lower(
        params, inst.cache, z, z, jnp.zeros((B, T), bool),
        position_keys(inst.base_key, zb, z), jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), bool), zb, zb)
    assert lowered.as_text().startswith(f"module @jit_seer_step_t{T} ")
    hlo = lowered.compile().as_text()
    assert hlo.startswith(f"HloModule jit_seer_step_t{T},")
    for scope in ("attention", "mlp", "sample", "accept"):
        assert f"jit(seer_step_t{T})/" in hlo
        assert f"/{scope}/" in hlo, scope


def test_crash_schedule_records_recovery_spans(tiny):
    """A seeded crash shows up in the trace: a fault_crash instant on
    the fault track, per-victim recovery instants stamped with the
    recovery-path kind, and a nonzero ``recovery`` phase — while the
    run still reproduces the no-fault oracle's tokens."""
    cfg, params, steps = tiny
    res_oracle, _, _ = _run(cfg, params, steps)
    inj = FaultInjector([FaultEvent(tick=2, kind="crash",
                                    instance_id="inst0", lose_pool=True)])
    tr = Tracer()
    res, _, _ = _run(cfg, params, steps, tracer=tr, injector=inj)
    assert res.responses() == res_oracle.responses()
    assert res.stats.instance_crashes == 1
    evs = tr.events()
    crashes = [e for e in evs if e["name"] == "fault_crash"]
    assert [e["track"] for e in crashes] == ["inst0"]
    assert crashes[0]["tick0"] == 2 and crashes[0]["args"]["lose_pool"]
    recov = [e for e in evs
             if e["name"] == "recovery" and e["ph"] == "i"]
    assert recov and all(e["args"]["kind"] in ("blob", "replay")
                         for e in recov)
    assert len(recov) == res.stats.recovered_requests
    tls = timelines_from_events(evs)
    rep = tail_attribution(tls)
    assert rep["conserved"]
    assert rep["phase_totals_s"].get("recovery", 0.0) > 0.0


# ---------------- simulator tier ---------------------------------------------


def test_simulator_emits_the_same_schema():
    from repro.configs import get_config
    from repro.core.simulator import ClusterSimulator, SimConfig
    from repro.data.workload import MOONLIGHT, make_workload

    spec = dataclasses.replace(MOONLIGHT, n_requests=16, group_size=4,
                               n_instances=2, max_gen_length=4096,
                               mean_gen_length=1000)
    tr = Tracer()
    sim = ClusterSimulator(
        get_config("yi-6b"), spec,
        SimConfig(mode="divided", policy="seer", max_slots=8,
                  chips_per_instance=1, kv_capacity_tokens=30_000,
                  chunk_size=512, fault_rate=0.05, seed=3),
        tracer=tr)
    sim.run(make_workload(spec, seed=3))
    evs = tr.events()
    assert evs and schema_keys(evs) == sorted(SCHEMA_KEYS)
    phases = {e["name"] for e in evs
              if e["cat"] == "request" and e["ph"] == "X"}
    assert phases <= set(PHASES)
    tls = timelines_from_events(evs)
    rep = tail_attribution(tls)
    assert rep["requests"] == 16 and rep["conserved"]
    # the modeled clock is explicit on every sim event
    assert all(e["t1"] >= e["t0"] for e in evs)


def test_simulator_trace_off_identical():
    from repro.configs import get_config
    from repro.core.simulator import ClusterSimulator, SimConfig
    from repro.data.workload import MOONLIGHT, make_workload

    spec = dataclasses.replace(MOONLIGHT, n_requests=12, group_size=4,
                               n_instances=2, max_gen_length=4096,
                               mean_gen_length=1000)
    sc = SimConfig(mode="divided", policy="seer", max_slots=8,
                   chips_per_instance=1, kv_capacity_tokens=30_000,
                   chunk_size=512, fault_rate=0.05, seed=3)

    def run(tracer):
        sim = ClusterSimulator(get_config("yi-6b"), spec, sc, tracer=tracer)
        r = sim.run(make_workload(spec, seed=3))
        return (r.total_time, r.tokens, r.preemptions, r.migrations,
                r.completion_times.tolist(), r.extras)

    assert run(None) == run(Tracer())


# ---------------- stats surface ----------------------------------------------


def test_rollout_stats_fields_documented_and_read():
    """The counter audit, mechanized: every RolloutStats field carries a
    one-line doc AND is read somewhere outside its own definition (src,
    benchmarks, scripts or other tests) — a counter nobody consumes is
    dead weight and fails here until it is either used or removed."""
    fields = dataclasses.fields(RolloutStats)
    assert fields, "RolloutStats lost its fields?"
    for f in fields:
        assert f.metadata.get("doc"), f"{f.name}: missing doc metadata"

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    corpus = []
    for sub in ("src", "benchmarks", "scripts", "tests"):
        for dirpath, _, names in os.walk(os.path.join(root, sub)):
            for n in names:
                if not n.endswith(".py") or n == "test_obs.py":
                    continue
                with open(os.path.join(dirpath, n)) as fh:
                    corpus.append((os.path.join(dirpath, n), fh.read()))
    for f in fields:
        n_reads = sum(text.count(f.name) for _, text in corpus)
        # rollout.py itself contains the definition plus the counter's
        # increments; a *consumed* counter appears in at least one more
        # file than src/repro/core/rollout.py
        files = [p for p, text in corpus
                 if f.name in text and not p.endswith("core/rollout.py")]
        assert files, f"RolloutStats.{f.name} is never read outside " \
            "its definition — dead counter"
        assert n_reads >= 2, f.name


def test_snapshot_is_the_field_set_plus_derived(tiny):
    cfg, params, steps = tiny
    res, _, _ = _run(cfg, params, steps)
    snap = res.stats.snapshot()
    field_names = {f.name for f in dataclasses.fields(RolloutStats)}
    assert set(snap) == field_names | {"mean_acceptance"}
    assert res.stats.as_dict() == snap
    nested = res.snapshot()
    assert set(nested) == {"rollout", "context", "pool", "dgds"}
    assert nested["rollout"] == snap
    json.dumps(nested)                         # bench-serializable
