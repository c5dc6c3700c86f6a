"""BENCHMARK.json against the files it names and the contract's shape:
each entry resolves by name to its configuration, traffic and metric
files, and each per-layer metric moves an end-to-end metric its cells
report."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|head|_dim$"
                   r"|_rank$|expansion|experts_per_tok)")


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][0] == "python3"
    for word in BM["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in BM["paths"])
    for p in BM["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_resolves_by_name(c):
    assert NAME.match(c["name"])
    assert c["file"] == f"bench/configs/{c['name']}.json"
    conf = json.loads((ROOT / c["file"]).read_text())
    assert conf["name"] == c["name"] and conf["source"] == c["source"]
    assert sorted(conf["reduced"]) == sorted(c["reduced"])
    assert not any(WIDTH.search(k) for k in c["reduced"])
    for part in ("reference", "adapters"):
        assert (ROOT / "bench" / part / f"{conf['reference']}.py").is_file()
    assert any(w["config"] == c["name"] for w in BM["workloads"])


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_by_name(w):
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert any(c["name"] == w["config"] for c in BM["configs"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = {m["name"] for m in harness.reported_end_to_end(BM, w["name"])}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.reported_per_layer(BM, w["name"])


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_resolves_and_moves_what_its_cells_report(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    assert callable(harness.load_reader(m["name"]).read)
    e2e = {x["name"] for x in BM["end_to_end"]}
    assert m["moves"] in e2e and m["moves"] != "setup_s"
    for cell in m["workloads"]:
        reported = {x["name"] for x in
                    harness.reported_end_to_end(BM, cell)}
        assert m["moves"] in reported
    if "roofline" in m["name"] or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_end_to_end_metrics():
    names = [m["name"] for m in BM["end_to_end"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["better"] in ("lower", "higher") and UNIT.match(m["unit"])


KEYS = {
    "configs": ({"name", "source", "file", "reduced", "why"}, set()),
    "workloads": ({"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": ({"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": ({"name", "unit", "better", "source", "layer", "moves"},
                  {"workloads"}),
}


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_have_exactly_the_contract_keys(section):
    need, may = KEYS[section]
    for e in BM[section]:
        assert need <= set(e) <= need | may, (section, e.get("name"))
    names = [e["name"] for e in BM[section]]
    assert len(names) == len(set(names))


def _texts():
    for section in ("configs", "workloads"):
        for e in BM[section]:
            yield e["why"]
    for c in BM["configs"]:
        yield c["source"]
    for m in BM["per_layer"]:
        yield m["layer"]
    yield from BM["command"]


def test_text_fields_are_one_short_line():
    for s in _texts():
        assert isinstance(s, str) and 1 <= len(s) <= 200, s
        assert "\n" not in s and "\t" not in s, s


def test_names_units_and_sizes():
    for c in BM["configs"]:
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BM["workloads"]:
        assert NAME.match(w["config"])
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len(BM["command"]) <= 32 and 1 <= len(BM["paths"]) <= 16
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells():
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (BM["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_at_most_half_the_cells_take_four_chips():
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BM["workloads"]) // 2)
