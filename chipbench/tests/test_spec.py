"""Discovery by name: a later PR adds a cell, a traffic mix, a configuration
and a layer metric as files of its own and edits no file that is there."""

import json
import os
import shutil

import pytest

from chipbench import spec


@pytest.fixture()
def copy(tmp_path):
    root = tmp_path / "checkout"
    os.makedirs(root)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(spec.BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return str(root)


def _hash_tree(root):
    out = {}
    for dp, _, fs in os.walk(os.path.join(root, "chipbench")):
        for f in fs:
            p = os.path.join(dp, f)
            out[p] = open(p, "rb").read()
    return out


def test_new_cell_mix_configuration_and_metric_are_found_with_no_edit(copy):
    before = _hash_tree(copy)
    bench = json.load(open(os.path.join(copy, "BENCHMARK.json")))
    # files of their own
    cfg = json.load(open(os.path.join(
        copy, "chipbench/configs/mistral7b-v03-d16.json")))
    cfg["engine"]["max_batch_size"] = 32
    json.dump(cfg, open(os.path.join(copy, "chipbench/configs/new-config.json"), "w"))
    mix = json.load(open(os.path.join(copy, "chipbench/traffic/chat_steady.json")))
    mix["arrivals"] = {"process": "gamma", "cv": 3.0, "rate_per_s": 6.0}
    json.dump(mix, open(os.path.join(copy, "chipbench/traffic/chat_burst.json"), "w"))
    with open(os.path.join(copy, "chipbench/layer_metrics/slots_active_mean.py"), "w") as f:
        f.write("def read(evidence):\n"
                "    s = evidence.get('util_samples') or []\n"
                "    return sum(x['slots_active'] for x in s) / len(s) if s else None\n")
    # entries in BENCHMARK.json (its entries may be added, none edited)
    bench["configs"].append({"name": "new-config", "source": cfg["source"],
                             "file": "chipbench/configs/new-config.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "new.chat_burst", "config": "new-config",
                               "traffic": "chat_burst", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "tpot_mean_ms"):
            m["workloads"].append("new.chat_burst")
    bench["per_layer"].append({
        "name": "slots_active_mean", "unit": "slots", "better": "higher",
        "source": "program_counter", "layer": "admission and batching (llm/paged.py)",
        "moves": "ttft_mean_ms", "workloads": ["new.chat_burst"]})
    json.dump(bench, open(os.path.join(copy, "BENCHMARK.json"), "w"))

    cell = spec.Cell("new.chat_burst", root=copy)
    assert cell.kind == "serve_open" and cell.config["engine"]["max_batch_size"] == 32
    assert cell.traffic["arrivals"]["process"] == "gamma"
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "ttft_mean_ms", "tpot_mean_ms", "setup_s"}
    assert [m["name"] for m in cell.metrics("per_layer")] == ["slots_active_mean"]
    got = spec.read_metrics(cell, "per_layer", "layer_metrics", {
        "util_samples": [{"slots_active": 10}, {"slots_active": 30}]})
    assert got == {"slots_active_mean": {"value": 20.0, "unit": "slots"}}
    # the runner of its kind is found by the traffic file's ``kind``
    assert hasattr(spec.load_module("kinds", cell.kind, copy), "run")
    # the generator takes the new mix as data
    from chipbench import loadgen
    reqs = loadgen.open_schedule(cell.traffic, 1, 10.0)
    assert len(reqs) == 60
    # nothing that was there changed
    after = _hash_tree(copy)
    assert all(after[p] == b for p, b in before.items())


def test_a_reader_that_finds_nothing_is_left_out(copy):
    cell = spec.Cell("m7b-d16.chat_steady", root=copy)
    got = spec.read_metrics(cell, "per_layer", "layer_metrics", {
        "rows": [], "util_samples": [], "trace": None, "ledger_after": None,
        "replica_up_s": 31.5})
    assert got == {"replica_up_s": {"value": 31.5, "unit": "s"}}


def test_unknown_names_are_errors():
    with pytest.raises(spec.BenchError):
        spec.Cell("no-such-cell")
    with pytest.raises(spec.BenchError):
        spec.load_module("kinds", "no-such-kind")


def test_no_list_of_cells_in_any_python_file():
    bench = spec.benchmark()
    names = ([w["name"] for w in bench["workloads"]]
             + [c["name"] for c in bench["configs"]]
             + [w["traffic"] for w in bench["workloads"]])
    for dp, _, fs in os.walk(spec.BENCH):
        if "tests" in dp.split(os.sep) or "__pycache__" in dp:
            continue
        for f in fs:
            if not f.endswith(".py"):
                continue
            text = open(os.path.join(dp, f)).read()
            code = "\n".join(ln for ln in text.splitlines()
                             if "--workload" not in ln and "default=" not in ln)
            for n in names:
                assert f'"{n}"' not in code and f"'{n}'" not in code, (f, n)
