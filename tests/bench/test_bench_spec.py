"""BENCHMARK.json keeps to the benchmark's format, and every name in it
resolves to an entry file that the harness finds by name."""
import json
import re
import shutil

import pytest

from bench.entries import ROOT, Entries, EntryError

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
MAX_CELLS = 24

SPEC = Entries().spec


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_command_and_paths():
    assert set(SPEC) == KEYS
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(one_line(w) and not w.startswith("/") and ".." not in w
               for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for word in SPEC["command"][1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_names_units_and_texts_use_the_allowed_characters():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        names += [c["name"]] + c["reduced"]
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and one_line(w["why"])
        names += [w["name"], w["config"], w["traffic"]]
    for kind in ("end_to_end", "per_layer"):
        for m in SPEC[kind]:
            names.append(m["name"])
            assert UNIT.match(m["unit"]), m["unit"]
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    for name in names:
        assert NAME.match(name), name
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({e["name"] for e in SPEC[kind]}) == len(SPEC[kind])
    metric_names = [m["name"] for k in ("end_to_end", "per_layer")
                    for m in SPEC[k]]
    assert len(set(metric_names)) == len(metric_names)
    for p in SPEC["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" not in f.parts:
                assert PATH.match(str(f.relative_to(ROOT))), f


def test_end_to_end_metrics_and_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and one_line(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_run_seconds_fit_a_full_check_of_every_cell():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * MAX_CELLS
    assert runs * (rs + 60) + MAX_CELLS * 2 * 90 + 1200 <= 43200


def test_every_cell_resolves_to_its_entry_files():
    entries = Entries()
    used = set()
    four = 0
    for cell in SPEC["workloads"]:
        cfg = entries.config(cell["config"])
        used.add(cell["config"])
        assert cfg["name"] == cell["config"]
        traffic = entries.traffic(cell["traffic"])
        assert {"touch_probability", "min_touched",
                "reland_share"} <= set(traffic)
        e2e = {m["name"] for m in entries.metrics_for(cell["name"],
                                                      "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = entries.metrics_for(cell["name"], "per_layer")
        assert per_layer
        for m in per_layer:
            assert callable(entries.metric(m["name"]).read)
            assert m["moves"] in e2e
        programs = set()
        for duet in cfg["deployment"]["benchmarks"]:
            work = entries.work(duet)
            ref = entries.reference(duet)
            assert set(work.VERSIONS) == {"v1", "v2"}
            assert callable(work.work) and callable(work.shapes)
            assert callable(ref.reference) and callable(ref.control)
            assert callable(ref.inputs)
            programs |= set(work.VERSIONS.values())
        want = {f"{p}_gap" for p in programs} | {
            "width_faults", "stats_faults", "ci_end_surprise",
            "pipeline_faults"}
        assert set(cfg["limits"]) == want
        rooflines = {m["name"][:-len("_roofline")] for m in per_layer
                     if m["name"].endswith("_roofline")}
        assert rooflines == programs
        four += cell["chips"] == 4
    assert used == {c["name"] for c in SPEC["configs"]}
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    assert all(any(f.startswith(p + "/") for p in SPEC["paths"])
               for f in files)


def test_a_new_entry_is_found_by_name_from_new_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / spec["configs"][0]["file"]).read_text())
    cfg["name"] = "ci-new"
    (tmp_path / "bench/configs/ci-new.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"touch_probability": 0.5, "min_touched": 1, "reland_share": 0.5}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    (tmp_path / "bench/work/new_duet.py").write_text(
        "VERSIONS = {'v1': 'a', 'v2': 'b'}\n"
        "def shapes(cfg):\n    return []\n"
        "def work(shapes):\n    return 1, 2\n")
    spec["configs"].append(dict(spec["configs"][0], name="ci-new",
                                file="bench/configs/ci-new.json"))
    spec["workloads"].append({"name": "ci-new.new-mix", "config": "ci-new",
                              "traffic": "new-mix", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "pipeline", "moves": "verdict_s",
                              "workloads": ["ci-new.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    entries = Entries(tmp_path)
    cell = entries.cell("ci-new.new-mix")
    assert entries.config(cell["config"])["name"] == "ci-new"
    assert entries.traffic(cell["traffic"])["reland_share"] == 0.5
    assert "new_metric" in [m["name"] for m in
                            entries.metrics_for("ci-new.new-mix",
                                                "per_layer")]
    assert entries.metric("new_metric").read(None) == 42.0
    assert entries.work("new_duet").work([]) == (1, 2)
    with pytest.raises(EntryError):
        entries.traffic("no-such-mix")
    with pytest.raises(EntryError):
        entries.metric("no_such_metric")
