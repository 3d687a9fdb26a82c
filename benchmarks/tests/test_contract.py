"""BENCHMARK.json against the files it names, and run.py against its rule."""

import json
import os
import re

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_has_its_file():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and os.path.isfile(os.path.join(run.ROOT, c["file"]))
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        with open(os.path.join(run.ROOT, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200 and w["chips"] in (1, 4)
        spec = run.load_spec(w["name"])
        t = spec["traffic"]
        for kind in ("generators", "drivers"):
            assert os.path.isfile(os.path.join(run.HERE, kind, t[kind[:-1]] + ".py"))
        assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert NAME.match(m["name"]) and m["moves"] in e2e
        assert os.path.isfile(os.path.join(run.HERE, "layers", m["name"] + ".py")), m["name"]
        assert callable(run.load_reader(m["name"]))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


def test_run_py_holds_no_cell():
    with open(os.path.join(run.HERE, "run.py")) as f:
        text = f.read()
    b = _bench()
    words = [w["name"] for w in b["workloads"]] + [c["name"] for c in b["configs"]]
    words += [w["traffic"] for w in b["workloads"]] + ["6000", "8192", "2400"]
    for word in words:
        assert word not in text, f"run.py names {word!r}"
