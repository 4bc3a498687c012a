"""BENCHMARK.json names only what the harness can find: every cell's
configuration and traffic file, and a reader for every metric."""
import json
import re

from chipbench import cells
from chipbench.cells import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
B = cells.benchmark()


def test_keys_and_names():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in B[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)


def test_cells_find_their_files():
    used = {w["config"] for w in B["workloads"]}
    assert used == {c["name"] for c in B["configs"]}
    for c in B["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        # every key changed from the source is listed, with the number
        # published and the one run
        assert c["reduced"] == conf["reduced"]
        for k in c["reduced"]:
            assert conf["published"][k] != conf["as_run"][k]
        cells.model_config(conf)        # every key is the program's
    for w in B["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()


def test_metrics_have_readers_and_move_an_end_to_end_metric():
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e
    for m in B["end_to_end"] + B["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").exists()
        assert m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in B["workloads"]}


def test_at_most_half_the_cells_ask_for_four_chips():
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)


def test_each_pair_of_config_and_traffic_is_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_every_cell_loads_and_serves_whole_groups_on_each_chip():
    from chipbench import harness
    for w in B["workloads"]:
        c = harness.cell(w["name"])
        assert c.chips == w["chips"]
        assert c.groups % c.chips == 0 and c.groups >= c.chips
