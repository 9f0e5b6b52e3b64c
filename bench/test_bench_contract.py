"""BENCHMARK.json keeps to its format, and every name in it leads to the
files that serve it."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parent.parent
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", *ENTRY_KEYS}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51


@pytest.mark.parametrize("group", list(ENTRY_KEYS))
def test_entries(group):
    names = [e["name"] for e in B[group]]
    assert len(names) == len(set(names))
    for e in B[group]:
        extra = {"workloads"} if group in ("end_to_end", "per_layer") \
            else set()
        assert ENTRY_KEYS[group] <= set(e) <= ENTRY_KEYS[group] | extra, e
        assert NAME.fullmatch(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e and group != "end_to_end" and group != "per_layer":
                assert line(e[key]), e[key]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]) and e["better"] in (
                "lower", "higher")


def test_configs_and_cells():
    cells = {w["name"]: w for w in B["workloads"]}
    configs = {c["name"]: c for c in B["configs"]}
    assert {w["config"] for w in cells.values()} == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(set(files)) == len(files)
    for c in configs.values():
        path = ROOT / c["file"]
        assert c["file"].startswith("bench/") and path.is_file()
        conf = json.loads(path.read_text())
        assert conf["reduced"] == c["reduced"]
        assert isinstance(conf["correct"]["max_rel_err"], float)
        family = harness.load_family(ROOT, conf["family"])
        for name in conf["subnets"]:
            family.flops_per_request(conf["model"], name)
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    assert len(pairs) == len(cells)
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 2)
    for w in cells.values():
        assert w["chips"] in (1, 4) and line(w["why"])
        assert NAME.fullmatch(w["traffic"]) and NAME.fullmatch(w["config"])
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "traffic" / f"{mix['kind']}.py").is_file()


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in B["end_to_end"] + B["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for m in B["per_layer"]:
        assert m["moves"] in e2e and line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= moved
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] \
                or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        reported = [m for m in B["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reported]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in B["per_layer"])


@pytest.mark.parametrize("entry", B["configs"], ids=lambda c: c["name"])
def test_config_names_a_family_with_the_whole_contract(entry):
    conf = json.loads((ROOT / entry["file"]).read_text())
    assert NAME.fullmatch(conf["family"])
    assert (ROOT / "bench" / "families" / f"{conf['family']}.py").is_file()
    # refuses a family that lacks a name of the contract
    harness.load_family(ROOT, conf["family"])


def test_a_family_without_the_whole_contract_is_refused(tmp_path):
    src = (ROOT / "bench" / "families" / "vit.py").read_text()
    fam = tmp_path / "bench" / "families" / "partial.py"
    fam.parent.mkdir(parents=True)
    fam.write_text(src + "\ndel rel_err\n")
    with pytest.raises(harness.BenchError, match="rel_err"):
        harness.load_family(tmp_path, "partial")
    with pytest.raises(harness.BenchError, match="missing"):
        harness.load_family(tmp_path, "absent")


# what only a model family may read: the ViT's image and token layout
MODEL_WORDS = re.compile(r"\b(img_res|patch|distill_token)\b")


@pytest.mark.parametrize("path", sorted(
    p.relative_to(ROOT).as_posix() for p in (ROOT / "bench").rglob("*.py")
    if "families" not in p.parts and not p.name.startswith("test_")))
def test_only_a_family_reads_the_model_layout(path):
    assert not MODEL_WORDS.findall((ROOT / path).read_text()), path
