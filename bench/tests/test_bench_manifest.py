"""BENCHMARK.json: every name resolves to its files, and keeps the rules."""
import re

import pytest

from bench.harness import manifest as M
from bench.harness.drivers import Driver

BENCH = M.manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./\-]{1,200}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"][1:]:
        assert any(word.startswith(p + "/") for p in BENCH["paths"])
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert (M.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files_by_name(cell):
    c = M.resolve(cell)
    assert issubclass(M.driver(c.traffic["driver"]), Driver)
    assert callable(M.generator(c.config["generator"]).triplets)
    assert set(c.spec["limits"]) and all(
        v > 0 for v in c.spec["limits"].values())
    for m in c.end_to_end + c.per_layer:
        reader = M.metric_reader(m["name"])
        assert reader.SOURCE == m["source"], m["name"]
        assert callable(reader.read)


def test_every_config_is_used_and_names_its_file():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["file"] == str(M.config_path(c["name"]).relative_to(M.ROOT))
        assert set(c["reduced"]) == set(M.load_json(M.config_path(
            c["name"]))["reduced"])
        assert len(c["reduced"]) <= 16
        files.add(c["file"])
    assert len(files) == len(BENCH["configs"])


@pytest.mark.parametrize("path", sorted(
    (M.BENCH / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_every_config_states_its_small_size(path):
    # the tests run every cell on the CPU at its configuration's ``small``
    cfg = M.load_json(path)
    assert cfg["small"] and set(cfg["small"]) <= set(cfg["params"])


def test_names_units_and_text_fields():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in METRICS])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for cell in m.get("workloads", []):
            assert cell in CELLS
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_end_to_end_metrics_and_bounds():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        needed = {"name", "unit", "better", "bound", "source"}
        assert needed <= set(m) <= needed | {"workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for cell in CELLS:
        reported = [m["name"] for m in M.resolve(cell).end_to_end]
        assert "setup_s" in reported and len(reported) >= 2, cell


def test_per_layer_metrics_move_a_metric_their_cells_report():
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        for cell in m.get("workloads", CELLS):
            reported = {e["name"] for e in M.resolve(cell).end_to_end}
            assert m["moves"] in reported, (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    for cell in CELLS:
        assert M.resolve(cell).per_layer, cell
    # metrics of one layer name it letter for letter alike
    assert all(len(v) == 1 for v in layers.values())


def test_metric_files_are_all_named_in_the_manifest():
    on_disk = {p.stem for p in (M.BENCH / "metrics").glob("*.py")}
    assert on_disk == {m["name"] for m in METRICS}


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        M.resolve("no.such.cell")
