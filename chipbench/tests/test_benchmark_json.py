"""``BENCHMARK.json`` against the contract it is written to."""

import os
import re

import pytest

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# the contract's widths, which ``reduced`` may never name: a hidden,
# intermediate, latent, state or projection size, a key that ends in ``_dim``
# or ``_rank``, a head size, an expansion factor, the experts a token
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|intermediate_size|head_size|"
                   r"d_state|d_head|d_conv|d_inner|expand|proj_size|"
                   r"experts_per_tok|num_attention_heads|num_key_value_heads|"
                   r"n_heads")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(w.startswith(("python", "chipbench/")) or "/" not in w
               for w in bench["command"])


def test_names_units_and_lines(bench):
    names = []
    for section, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                          ("workloads", {"name", "config", "traffic", "chips", "why"})):
        for e in bench[section]:
            assert set(e) == keys, e
            names.append(e["name"])
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for e in bench["workloads"]:
        assert NAME.match(e["config"]) and NAME.match(e["traffic"])
        assert e["chips"] in (1, 4)
    for e in bench["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert e["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= e["bound"] <= 0.1
    for e in bench["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert e["source"] in SOURCES
        assert 1 <= len(e["layer"]) <= 200
    for e in bench["end_to_end"] + bench["per_layer"]:
        names.append(e["name"])
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])


def test_cells_pair_once_and_four_chip_share(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(pairs) // 4)
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_every_file_a_cell_names_exists(bench):
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"])
        assert cell.config_entry["file"].startswith("chipbench/")
        assert os.path.exists(os.path.join(
            spec.BENCH, "kinds", cell.kind + ".py"))
        for m in cell.metrics("end_to_end"):
            assert os.path.exists(os.path.join(
                spec.BENCH, "end_to_end", m["name"] + ".py")), m["name"]
        for m in cell.metrics("per_layer"):
            assert os.path.exists(os.path.join(
                spec.BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
        for key in cell.config_entry["reduced"]:
            assert key in cell.config["reduced"], key
            assert not WIDTH.search(key), key  # vocab_size is no width


def test_moves_is_reported_wherever_the_layer_metric_is(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    all_cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", all_cells):
            assert cell in all_cells
            assert cell in moved.get("workloads", all_cells), (m["name"], cell)


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"])
        e2e = [m["name"] for m in cell.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert len(cell.metrics("per_layer")) >= 1


def test_configuration_files_state_their_cut(bench):
    for c in bench["configs"]:
        f = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert f["source"] == c["source"]
        for key in ("reduced", "assumed", "deployment", "chips", "published"):
            assert key in f, (c["name"], key)
        assert set(c["reduced"]) == set(f["reduced"])
        # no width is cut, whatever the architecture: a cut key is listed
        # with its published value, and none of them is a width
        assert set(f["published"]) == set(f["reduced"]), c["name"]
        widths = [k for k, v in f.items()
                  if WIDTH.search(k) and isinstance(v, (int, float))]
        assert widths and "hidden_size" in widths, c["name"]
        assert not [k for k in f["published"] if WIDTH.search(k)], c["name"]
        if "Mistral-7B-v0.3" in c["source"]:  # as published
            assert (f["hidden_size"], f["intermediate_size"], f["head_dim"],
                    f["num_attention_heads"], f["num_key_value_heads"],
                    f["vocab_size"]) == (4096, 14336, 128, 32, 8, 32768)


def test_the_serving_gates_are_the_pace_mean_the_tail_and_the_first_token(bench):
    """PR 46: ``tpot_mean_ms`` in every serving cell, the tail only where a
    window holds ten streams beyond its percentile, ``tpot_p95_ms`` per
    layer; every cell's traffic says how many requests a window holds."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "tpot_p95_ms" not in e2e
    serving = [w["name"] for w in bench["workloads"]
               if spec.Cell(w["name"]).traffic.get("loop") == "open"]
    assert sorted(e2e["tpot_mean_ms"]["workloads"]) == sorted(serving)
    assert sorted(e2e["ttft_mean_ms"]["workloads"]) == sorted(serving)
    assert e2e["tpot_mean_ms"]["bound"] <= 0.03
    assert e2e["ttft_mean_ms"]["bound"] <= 0.10
    # every serving cell has ONE tail of the pace, at the highest of the two
    # percentiles that has ten streams beyond it in its window
    tails = {"tpot_p90_ms": 0.10, "tpot_p85_ms": 0.15}
    for name in serving:
        requests = (spec.Cell(name).traffic["arrivals"]["rate_per_s"]
                    * bench["run_seconds"])
        mine = [t for t in tails if name in e2e[t]["workloads"]]
        want = next(t for t, beyond in tails.items()
                    if requests * beyond >= 10)
        assert mine == [want], (name, requests)
    for t in tails:
        assert e2e[t]["bound"] <= 0.05
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in ("tpot_p95_ms", "stream_gap_max_ms"):
        assert per[name]["moves"] == "tpot_mean_ms"
        assert per[name]["source"] == "host_clock"
        assert sorted(per[name]["workloads"]) == sorted(serving)
