"""BENCHMARK.json and the files it names: the names, units and sizes the
benchmark's format allows, and a file for every configuration, traffic mix
and metric."""

import importlib.util
import json
import os
import re

import pytest

from chip_small import CHIP, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|per_tok|entry_bits|"
                   r"page_bits|entry_bytes|page_bytes")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd[1:]:
        assert not w.startswith("/") and ".." not in w
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w.startswith(p + "/") for p in bench["paths"])
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    assert len(bench["end_to_end"]) <= 16 and len(bench["per_layer"]) <= 128


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert config["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
        used = [w for w in bench["workloads"] if w["config"] == c["name"]]
        assert used, c["name"]


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(CHIP, "traffic",
                                           w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def _reader(name):
    path = os.path.join(CHIP, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(
        ".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert callable(_reader(m["name"]))
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        assert callable(_reader(m["name"]))
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w]), (m, w)
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_every_cell_reports_enough(bench):
    import run as runner
    for w in bench["workloads"]:
        spec = runner.load_cell(w["name"])
        names = [m["name"] for m in spec["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert spec["per_layer"], w["name"]


def test_traffic_files_name_a_generator_and_limits(bench):
    from chipbench import generator
    for w in bench["workloads"]:
        with open(os.path.join(CHIP, "traffic", w["traffic"] + ".json")) as f:
            t = json.load(f)
        kind = generator.KINDS[t["generator"]]
        if issubclass(kind, generator.TuneBase):
            for n in ("score_gap", "opt_gap_mean"):
                assert isinstance(t["limits"][n], float), (w["name"], n)


def test_every_reader_reads_a_run(bench):
    """Every metric's reader turns a run's records, counters and trace
    reduction into a finite positive number (or, without a trace, nothing
    for a trace metric)."""
    import run as runner
    from chipbench.xplane import Reduction
    from repro.lsm import IOStats
    io = IOStats(random_reads=1200, comp_pages_read=300,
                 comp_pages_written=400, queries={"z0": 0, "z1": 1000,
                                                  "q": 0, "w": 500})
    counters = {"io": io, "reads": 1000, "writes": 500, "f_a": 1.0,
                "f_seq": 1.0, "select_s": [0.25, 0.27]}
    records = [(0.0, 0.5, 90, True), (0.5, 1.1, 90, True)]
    trace = Reduction(window_s=1.0, busy_s=0.2, devices=1,
                      modules={"jit__solve_many": (4, 0.1)}, idle_gaps=[],
                      spans={})
    for w in bench["workloads"]:
        spec = runner.load_cell(w["name"])
        for traced, group in ((None, "end_to_end"), (trace, "per_layer")):
            ctx = runner.Context(spec, 12.5, records, counters, traced)
            for m in spec[group]:
                v = runner.metric_reader(m["name"])(ctx)
                assert v is not None and 0 < v < float("inf"), m["name"]
        ctx = runner.Context(spec, 12.5, records, counters, None)
        for m in spec["per_layer"]:
            if m["source"] == "device_trace":
                assert runner.metric_reader(m["name"])(ctx) is None


def test_peaks_table():
    from chipbench.peaks import peaks
    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks("cpu")
