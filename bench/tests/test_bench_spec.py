"""BENCHMARK.json's names, units and texts within their allowed
characters and lengths, and every file a cell names present under the
benchmark."""
import json
import os
import re

import pytest

from bench import harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")
BENCH = H.benchmark()


def names():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[key]:
            yield e["name"]
    for w in BENCH["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(names())))
def test_name_characters(name):
    assert NAME.match(name), name


def test_units_and_texts():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
        assert "\t" not in e["why"]
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200


def test_unique_names_and_paths():
    for key in ("configs", "workloads"):
        ns = [e["name"] for e in BENCH[key]]
        assert len(ns) == len(set(ns))
    ms = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(ms) == len(set(ms))
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_every_cell_has_its_files():
    for w in BENCH["workloads"]:
        H.cell_spec(w["name"], BENCH)
        traffic = H.load_json("traffic", w["traffic"] + ".json")
        assert os.path.exists(os.path.join(H.BENCH, "drivers",
                                           traffic["kind"] + ".py"))
    for m in BENCH["per_layer"]:
        assert hasattr(H.load_module("metrics", m["name"]), "read")
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_setup_bound_and_run_seconds():
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    assert ends["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in ends.values())
    assert 1 <= BENCH["run_seconds"] <= 51
