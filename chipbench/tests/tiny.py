"""A tiny cell for the CPU tests: the shape of the benchmark's cell (a
dense decoder under the grpo mix) at a size a test run can hold."""
from chipbench import cells
from chipbench.harness import Cell

DENSE = dict(arch_type="dense", num_layers=2, d_model=128, num_heads=4,
             num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512,
             rope_theta=10000.0, rms_eps=1e-5, tie_embeddings=True,
             sliding_window=0, dtype="bfloat16", param_dtype="bfloat16")
PROGRAM = "granite-3-8b"
# the check's limit at this size, between its two readings on the CPU:
# the program's widest gap 0 to 0.0036, the float8 control's 0.038 to
# 0.10, over four content seeds
LIMIT = 0.02


def conf(model: dict = DENSE, limit: float = LIMIT) -> dict:
    return {"name": "tiny-dense", "program_config": PROGRAM,
            "reference": "granite", "model": dict(model),
            "serving": {"max_slots": 4, "cache_len": 128, "groups": 2,
                        "max_logit_gap": limit}}


def mix() -> dict:
    m = cells.load_traffic(cells.benchmark()["workloads"][0]["traffic"])
    # as in the benchmark's mix, the prompt and the first chunk pass half
    # the cache, so every export lands in one bucket
    return dict(m, prompt_len=56, group_size=4, warm_budget=40,
                lengths=dict(m["lengths"], scale_divisor=1024),
                rollout=dict(m["rollout"], chunk_size=16))


def cell(model: dict = DENSE, limit: float = LIMIT) -> Cell:
    return Cell("tiny-dense", conf(model, limit), mix(), 1)


# the benchmark's cell of several one-chip instances, whose metrics a
# tiny cell of that name reports
INSTANCES_CELL = "granite-3-8b.pp2.grpo.4x1"


def instances_cell(chips: int = 2) -> Cell:
    """The tiny cell as ``chips`` one-chip instances behind one scheduler,
    under the name of the benchmark's multi-instance cell."""
    return Cell(INSTANCES_CELL, conf(), mix(), chips)
