"""BENCHMARK.json and the files it names: every piece a cell needs is
there, found by name, and every name and unit is of the allowed kind."""
import json
import os
import re

import pytest

from harness.cells import BENCH_DIR, CHECKOUT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(bench):
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in bench["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k


def test_every_cell_finds_its_pieces(bench):
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.driver().run
        assert os.path.exists(os.path.join(BENCH_DIR, "cells",
                                           f"{w['name']}.json"))
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]


@pytest.mark.parametrize("metric", ["input_wait_ms.train", "assemble_roofline",
                                    "train_mfu_pct", "device_idle_pct.train",
                                    "resume_read_gb_per_s",
                                    "resume_stage_gb_per_s"])
def test_every_per_layer_metric_has_a_reader(bench, metric):
    from harness.cells import metric_reader

    assert metric in {m["name"] for m in bench["per_layer"]}
    assert callable(metric_reader(metric).read)


def test_bounds_within_the_contract(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    assert 1 <= bench["run_seconds"] <= 51
