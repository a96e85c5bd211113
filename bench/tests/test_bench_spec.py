"""``BENCHMARK.json`` against the benchmark's rules, every cell found by
name, and the refusals of ``bench/run.py``."""

import json
import re
import types
from pathlib import Path

import pytest

from bench import generate, run as harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
WIDTH = re.compile(r".*(_dim|_rank)$|^(d_model|hidden_size|d_state|headdim|expand|"
                   r"intermediate_size|num_experts_per_tok)$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells must fit its 43200 s
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_names_units_and_keys():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                        ("workloads", {"name", "config", "traffic", "chips", "why"}),
                        ("end_to_end", {"name", "unit", "better", "bound", "source"}),
                        ("per_layer", {"name", "unit", "better", "source", "layer", "moves"})):
        for entry in SPEC[group]:
            assert set(entry) - {"workloads"} == keys, entry
            assert NAME.fullmatch(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
            for k in ("why", "layer", "source"):
                if k in entry:
                    assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
            if "unit" in entry:
                assert UNIT.fullmatch(entry["unit"])
                assert entry["better"] in ("lower", "higher")


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        for cell in m.get("workloads", []):
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_configs_and_cells():
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in configs.values():
        assert c["file"].startswith("bench/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert not [k for k in c["reduced"] if WIDTH.match(k)]
        assert len(c["reduced"]) <= 16
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert all(w["chips"] in (1, 4) for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_found_by_name(cell):
    found = harness.resolve(cell)
    names = {m["name"] for m in found["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert found["per_layer"]
    for m in found["per_layer"]:
        assert callable(harness._load_module(m["reader"], "probe").read)
    entry = harness.importlib.import_module(f"bench.entries.{found['traffic']['entry']}")
    assert entry.Cell.unit_metric in names
    assert set(found["traffic"]["limits"])


def test_unknown_cell_is_refused():
    with pytest.raises(harness.SpecError):
        harness.resolve("no.such.cell")


def _fake_jax(platform, kind, n=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * n)


def test_refuses_cpu_few_chips_and_unknown_kind():
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())
    with pytest.raises(harness.SpecError, match="needs a TPU"):
        harness.check_devices(_fake_jax("cpu", "cpu"), 1, peaks)
    with pytest.raises(harness.SpecError, match="asks for 4"):
        harness.check_devices(_fake_jax("tpu", "TPU v5 lite", 1), 4, peaks)
    with pytest.raises(harness.SpecError, match="no peaks"):
        harness.check_devices(_fake_jax("tpu", "TPU v9 imaginary"), 1, peaks)
    got = harness.check_devices(_fake_jax("tpu", "TPU v5 lite", 4), 4, peaks)
    assert got == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_main_without_tpu_prints_no_result(capsys):
    rc = harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_seeds_past_32_bits_differ():
    import jax

    a = jax.random.key_data(generate.seed_key(7))
    b = jax.random.key_data(generate.seed_key(7 + 2 ** 32))
    assert (a != b).any()
