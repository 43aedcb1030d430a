"""BENCHMARK.json resolves to the files the harness finds by name, keeps
to the contract's shapes, and no run can write past the disk budget."""
import json
import math
import os
import re
import sys

import jax
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import jobs  # noqa: E402
import reference  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = SPEC["workloads"]
CONFIGS = {c["name"]: c for c in SPEC["configs"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
#: a chip machine that has written this much (deleted files included)
#: is swapped for a fresh one, which compiles again
FRESH_MACHINE = 30 * 2 ** 30
#: runs one machine must hold under that: two pairs, each a run of the
#: parent and a run of the change
RUNS_PER_MACHINE = 4


def _load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _traffic(cell):
    return _load(os.path.join("bench", "traffic", cell["traffic"] + ".json"))


def _state_bytes(model):
    """bf16 params + fp32 master, m, v: 14 B a parameter."""
    shapes = reference.param_shapes(model)
    n = sum(math.prod(s) for s in
            jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple)))
    return 14 * n


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_cell_resolves(cell):
    conf = _load(CONFIGS[cell["config"]]["file"])
    assert conf["name"] == cell["config"]
    traffic = _traffic(cell)
    assert traffic["kind"] in jobs.JOBS
    assert cell["chips"] in (1, 4)
    reported = [m for m in SPEC["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    layers = [m for m in SPEC["per_layer"]
              if cell["name"] in m.get("workloads", [cell["name"]])]
    assert layers
    for met in layers:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           met["name"] + ".py"))
        assert met["moves"] in [m["name"] for m in reported]


def test_names_and_units():
    names = [c["name"] for c in SPEC["configs"]] + \
        [c["name"] for c in CELLS] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_worst_case_bytes_per_run(cell):
    """Steady: the warm-up save plus at most ``max_intervals`` window
    saves; resume: one save. Two pairs of runs stay under the fresh
    machine mark, whatever speed a later change gives the save or the
    step."""
    conf = _load(CONFIGS[cell["config"]]["file"])
    traffic = _traffic(cell)
    saves = 1 + traffic.get("max_intervals", 0)
    assert RUNS_PER_MACHINE * saves * _state_bytes(conf["model"]) \
        < FRESH_MACHINE


@pytest.mark.parametrize("conf", SPEC["configs"],
                         ids=[c["name"] for c in SPEC["configs"]])
def test_reduced_keys_are_what_changed(conf):
    """Every size changed from the published model is listed, and no
    width is."""
    f = _load(conf["file"])
    changed = sorted(k for k, v in f["published"].items()
                     if k in f["model"] and f["model"][k] != v)
    assert changed == sorted(conf["reduced"]) == sorted(f["reduced"])
    for k in conf["reduced"]:
        assert not re.search(r"(d_model|d_ff|heads|_dim|_rank|experts)", k)
