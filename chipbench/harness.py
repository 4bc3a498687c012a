"""One run of one cell: set up, measure a window, check the outputs.

The window is driven by the module of the traffic mix's kind
(``kinds/<kind>.py``), found by name: it warms up every shape the window
uses, then serves the mix, in the order ``--seed`` picks, until the
deadline.

Everything a metric needs is gathered into one ``readings`` dict (below);
each metric is a reader of its own under ``metrics/``, found by name.
After the window, the program's state is freed and a sample of the
greedy requests it finished is scored by the configuration's plain
reference (``reference/``): the widest gap by which a served token's
logit lies below the reference's best decides ``correct``.

``readings`` keys:
  setup_s, window_s            host seconds: process start to window
                               start, and the window itself
  instances                    one-chip instances, one per chip
  tokens                       output tokens committed in the window
  engine_steps                 fused steps the instances ran, together
  row_slots_active/_total      rows carrying work / rows computed
  drafted, accepted            speculative draft tokens and acceptances
  chunks, migrations           chunks completed, and those re-admitted on
                               another instance
  latencies                    per request: t_finished - iteration start
  iterations                   per iteration: makespan and sorted finish
                               latencies
  model_flops                  forward FLOPs of the window's requests
  peak_flops                   the chips' published bf16 peak, summed
  memory_peak_bytes, hbm_bytes peak_bytes_in_use after the window, and
                               the bytes_limit, of the fullest chip
  trace                        (traced runs) busy_s, window_s,
                               device_ops, idle_gaps; else None
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import shutil
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

import numpy as np

from chipbench import cells, flops
from chipbench.peaks import PEAKS, peaks

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE / ".trace"
CORRECT_TOKENS = 400       # served tokens the reference scores per run


class NoChip(RuntimeError):
    """JAX found no accelerator of a known kind, or too few of them."""


@dataclass
class Cell:
    """A cell of ``chips`` chips runs one one-chip instance on each, all
    behind one scheduler and one KV pool."""
    name: str
    conf: dict              # configs/<config>.json
    mix: dict               # traffic/<traffic>.json
    chips: int

    @property
    def groups(self) -> int:
        """Groups per iteration: each instance's share, as many times as
        there are instances."""
        return self.chips * min(self.mix["max_groups"],
                                self.conf["serving"]["groups"])

    @property
    def content_seed(self) -> int:
        """The mix's fixed seed of the weights and of the batch's prompts
        and budgets; ``--seed`` only orders them."""
        return self.mix["content_seed"]


def cell(name: str) -> Cell:
    """The cell, refused before any set-up where its model cannot be
    counted (``flops.check``)."""
    w = cells.workload(name)
    conf = cells.load_config(w["config"])
    flops.check(conf)
    return Cell(name, conf, cells.load_traffic(w["traffic"]), w["chips"])


def devices(chips: int, require_chip: bool = True):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or devs[0].device_kind not in PEAKS):
        raise NoChip(f"no known accelerator: {devs[0].platform} "
                     f"{devs[0].device_kind!r}")
    if require_chip and len(devs) < chips:
        raise NoChip(f"{chips} chips asked for, {len(devs)} found")
    return devs


def load_kind(kind: str):
    """The window driver of a traffic mix's ``kind`` (``kinds/<kind>.py``):
    ``warm(ro, cell, seed, vocab)`` and ``window(ro, cell, seed, vocab,
    deadline, readings, log) -> served requests``."""
    return importlib.import_module(f"chipbench.kinds.{kind}")


def build(c: Cell, seed: int, devs=None):
    """Weights from the mix's content seed, made on the device, and the
    rollout: one instance on each of the cell's chips (``devs``, default
    JAX's devices; a device repeats where there are fewer)."""
    import jax

    from repro.core import SeerRollout
    from repro.launch.serve import init_params_on_device
    devs = devs or jax.devices()
    cfg = cells.model_config(c.conf)
    params = init_params_on_device(cfg, c.content_seed % (1 << 32))
    jax.block_until_ready(params)
    s = c.conf["serving"]
    r = c.mix["rollout"]
    ro = SeerRollout(cfg, params, n_instances=c.chips,
                     devices=[devs[i % len(devs)] for i in range(c.chips)],
                     max_slots=s["max_slots"],
                     cache_len=s["cache_len"], chunk_size=r["chunk_size"],
                     policy=r["policy"], spec_decode=r["spec_decode"],
                     base_seed=seed % (1 << 31))
    return cfg, params, ro


GAMMA_BUCKETS = (0, 1, 2, 4, 8, 16, 32)   # the engine's draft buckets


def warm_programs(ro, extent: int) -> None:
    """Compile every program the window can ask for, whatever the seed:

    * a fused step of each width the engine can pick, with its sampling
      keys, every row masked out: one column plus each draft bucket up
      to ``gamma_max``, and each power of two up to a whole prefill
      chunk.  The engine rounds the widest prefill piece of a step up to
      a power of two, and the per-step prefill budget cuts prompts into
      pieces that depend on which slots prefill together, so every such
      width can come up on some seed;
    * chunk exports and imports of each number of slots that migrate
      together, with the engine's conversion of their slot list, at the cache's whole extent (a mix whose prompt and first
      chunk pass half the cache exports at no other bucket), each slot
      written back as it was.

    Which programs a step or a migration needs follows from the traffic
    and the engine's buckets; a mix outside these rules shows as
    ``window_compiles``."""
    import jax.numpy as jnp
    from repro.engine.sampling import position_keys
    for inst in ro.instances:
        B, steps = inst.max_slots, inst.steps
        widths = {g + 1 for g in GAMMA_BUCKETS if g <= inst.gamma_max}
        b = 1
        while b <= inst.prefill_chunk:
            widths.add(b)
            b <<= 1
        widths.add(inst.prefill_chunk)
        for T in sorted(widths):
            z = jnp.zeros((B, T), jnp.int32)
            zb = jnp.zeros((B,), jnp.int32)
            keys = position_keys(inst.base_key, zb, z)
            *_, inst.cache = steps.fused_step(T)(
                inst.params, inst.cache, z, z, jnp.zeros((B, T), bool),
                keys, jnp.zeros((B,), jnp.float32), jnp.zeros((B,), bool),
                zb, zb)
        for n in range(1, B + 1):
            # the slots go over as the engine sends them: a list turned
            # into an int32 array, which compiles a conversion per length
            slots = jnp.asarray(list(range(n)), jnp.int32)
            blobs = steps.export_batch((extent,) * n)(inst.cache, slots)
            inst.cache = steps.import_batch()(inst.cache, slots, blobs)


def load_reader(metric: str) -> Callable[[dict], Optional[float]]:
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(cell_name: str, trace: bool) -> List[dict]:
    bench = cells.benchmark()
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def counters(ro) -> dict:
    out = {k: 0 for k in ("engine_steps", "row_slots_active",
                          "row_slots_total")}
    for inst in ro.instances:
        out["engine_steps"] += inst.steps_run
        out["row_slots_active"] += inst.row_slots_active
        out["row_slots_total"] += inst.row_slots_total
    return out


def measure(c: Cell, seed: int, seconds: float, trace: bool, t_start: float,
            *, require_chip: bool = True, control: bool = False,
            log=print) -> dict:
    """Set up, run the window, score the outputs; the result line.

    ``control``: the float8 control takes the program's place in the
    ``max_logit_gap`` check (the program's own reading goes under
    ``program_gap``); the benchmark's runs never do this."""
    import jax

    devs = devices(c.chips, require_chip)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import CompileClock
    enable_compile_cache()
    # cache every program, however fast it compiled, so a later run's
    # set-up reads all of them back
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cfg, params, ro = build(c, seed, devs)
    log("weights made on the device")
    vocab = cfg.vocab_size
    kind = load_kind(c.mix["kind"])
    with CompileClock() as warm_clock:
        warm_programs(ro, c.conf["serving"]["cache_len"])
        log(f"programs warmed: {warm_clock.compiles} compiled, "
            f"{warm_clock.seconds:.2f} s compiling or reading the cache")
        kind.warm(ro, c, seed, vocab)
    log(f"warm-up: {warm_clock.compiles} programs compiled, "
        f"{warm_clock.seconds:.2f} s compiling or reading the cache")

    readings = {}
    before = counters(ro)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    from chipbench.trace_reduce import WINDOW_EVENT
    # a program compiled inside the window is named on standard error
    jax.config.update("jax_log_compiles", True)
    with CompileClock() as clock, jax.profiler.TraceAnnotation(WINDOW_EVENT):
        t_w0 = time.monotonic()
        readings["setup_s"] = t_w0 - t_start
        served = kind.window(ro, c, seed, vocab, t_w0 + seconds, readings,
                             log)
        readings["window_s"] = time.monotonic() - t_w0
    jax.config.update("jax_log_compiles", False)
    if trace:
        jax.profiler.stop_trace()
    after = counters(ro)
    readings.update({k: after[k] - before[k] for k in after})
    log(f"window: {len(served)} requests, {readings['tokens']} tokens, "
        f"{readings['engine_steps']} steps, {readings['window_s']:.3f} s, "
        f"{clock.compiles} programs compiled inside it")

    readings["instances"] = len(ro.instances)
    # the fullest of the cell's chips
    used = {inst.device for inst in ro.instances}
    dev, mem = max(((d, d.memory_stats() or {}) for d in used),
                   key=lambda dm: dm[1].get("peak_bytes_in_use", 0))
    p = peaks(dev.device_kind) if require_chip else None
    readings["memory_peak_bytes"] = mem.get("peak_bytes_in_use")
    # the device's own allocator limit where it reports one, else the
    # published size
    readings["hbm_bytes"] = mem.get("bytes_limit") or (p.hbm_bytes if p
                                                       else None)
    readings["peak_flops"] = len(used) * p.bf16_flops if p else None
    readings["model_flops"] = flops.flops_for_requests(
        c.conf, [(len(r.prompt), len(r.generated)) for r in served])
    readings["trace"] = None
    if trace:
        readings["trace"] = reduce_trace()

    bad = [r.req_id for r in served
           if not r.finished or len(r.generated) != r.max_new_tokens
           or any(t < 0 or t >= vocab for t in r.generated)]
    sample = sample_for_check(served, seed)
    n_served = len(served)
    del ro, params, served
    gc.collect()
    gap, control_gap = score(c, sample, control=control, log=log)
    # the control stands in the program's place: its first choices are
    # held to the same limit, and must come out not correct
    checks = {
        "requests_short": {"value": len(bad), "limit": 0},
        "window_compiles": {"value": clock.compiles, "limit": 0},
        "max_logit_gap": {"value": control_gap if control else gap,
                          "limit": c.conf["serving"]["max_logit_gap"]},
    }
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    out_metrics = {}
    for m in metrics_for(c.name, trace):
        v = load_reader(m["name"])(readings)
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs) if c.chips > 1 else 1,
              "memory_peak_bytes": readings["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": n_served,
            "failed": len(bad), "metrics": out_metrics,
            "device": device}
    if trace and readings["trace"]:
        t = readings["trace"]
        device["busy_s"], device["window_s"] = t["busy_s"], t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    if control:
        line["program_gap"] = gap
    line["checks"] = checks
    return line


def reduce_trace() -> Optional[dict]:
    from chipbench.trace_reduce import reduce_xspace
    found = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    try:
        return reduce_xspace(str(found[-1])) if found else None
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


def sample_for_check(finished, seed: int):
    """Greedy requests to score: the longest, then others drawn from the
    seed until :data:`CORRECT_TOKENS` served tokens are in the sample.
    Each is (prompt, served tokens) on the host."""
    greedy = [r for r in finished if r.temperature == 0 and r.generated]
    if not greedy:
        return []
    greedy.sort(key=lambda r: r.req_id)
    first = max(greedy, key=lambda r: len(r.generated))
    rng = np.random.default_rng([seed & 0xFFFFFFFFFFFF, 3])
    order = [first] + [greedy[i] for i in rng.permutation(len(greedy))
                       if greedy[i] is not first]
    out, n = [], 0
    for r in order:
        if n >= CORRECT_TOKENS:
            break
        out.append((list(r.prompt), list(r.generated)))
        n += len(r.generated)
    return out


def reference_module(conf: dict):
    return importlib.import_module(
        f"chipbench.reference.{conf['reference']}")


def padded_batch(sample, length: int):
    rows = -(-len(sample) // 8) * 8           # one compile per bucket
    toks = np.zeros((rows, length), np.int32)
    for i, (p, g) in enumerate(sample):
        seq = p + g
        toks[i, :len(seq)] = seq
    return toks


def gaps_under(c: Cell, sample, modes=("f32",)):
    """Per sampled request, the gap of each served token under the
    float32 reference; with ``fp8`` in ``modes`` also the gap of the token
    the float8 control puts first at the same positions."""
    import jax
    import jax.numpy as jnp
    ref = reference_module(c.conf)
    m = c.conf["model"]
    length = max(len(p) + len(g) for p, g in sample)
    length = -(-length // 128) * 128          # one compile per bucket
    toks = padded_batch(sample, length)
    with jax.default_matmul_precision("highest"):
        lf = ref.logits(m, c.content_seed, toks)
        best = lf.max(-1)
        nxt = jnp.asarray(np.roll(toks, -1, axis=1))
        served = np.asarray(best - jnp.take_along_axis(
            lf, nxt[..., None], -1)[..., 0])
        control = None
        if "fp8" in modes:
            top8 = jnp.argmax(ref.logits(m, c.content_seed, toks, "fp8"),
                              -1)
            control = np.asarray(best - jnp.take_along_axis(
                lf, top8[..., None], -1)[..., 0])
    out_served, out_control = [], []
    for i, (p, g) in enumerate(sample):
        # position j predicts token j + 1: the served tokens are
        # predicted at positions len(p) - 1 .. len(p) + len(g) - 2
        sl = slice(len(p) - 1, len(p) + len(g) - 1)
        out_served.append(served[i, sl])
        if control is not None:
            out_control.append(control[i, sl])
    return out_served, (out_control if control is not None else None)


def score(c: Cell, sample, control: bool = False, log=print):
    """The widest gap of a served token under the reference (inf when
    nothing greedy was served: a run that cannot be checked fails), and
    with ``control`` that of the float8 control's first choices."""
    if not sample:
        return float("inf"), None
    t0 = time.monotonic()
    served, ctl = gaps_under(c, sample,
                             ("f32", "fp8") if control else ("f32",))
    gap = float(max(s.max() for s in served))
    ctl_gap = float(max(s.max() for s in ctl)) if ctl else None
    log(f"reference: {len(sample)} requests, "
        f"{sum(len(s) for s in served)} served tokens, widest gap "
        f"{gap:.6g}" + (f", control {ctl_gap:.6g}" if ctl else "")
        + f", {time.monotonic() - t0:.1f} s")
    return gap, ctl_gap
