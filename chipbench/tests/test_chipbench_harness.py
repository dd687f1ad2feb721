"""BENCHMARK.json, the files it names, and the run's refusal without a chip (CPU)."""
import ast
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_has_exactly_the_contract_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e


def test_every_file_a_cell_names_exists():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert (BENCH / "families" / f"{cfg['family']}.py").exists()
    for w in SPEC["workloads"]:
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert traffic["chips"] == w["chips"]
        assert (BENCH / "configs" / f"{w['config']}.json").exists()
        limits = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        assert set(limits["limits"]) == {"loss_end", "eta_target_r1", "change"}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name'].split('.')[0]}.py").exists()
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in SPEC["workloads"]}


def test_a_configuration_without_a_family_is_refused():
    from chipbench import cell

    cfg = json.loads((ROOT / SPEC["configs"][0]["file"]).read_text())
    assert cell.family(cfg).__name__ == f"chipbench.families.{cfg['family']}"
    del cfg["family"]
    with pytest.raises(KeyError, match="names no"):
        cell.family(cfg)


def test_sigma_follows_the_configuration_rule():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["sigma"] == pytest.approx(
            cfg["noise_multiplier"] * cfg["clip"] / cfg["clients"] ** 0.5, rel=1e-12)


def test_the_yardstick_imports_no_benchmark_script():
    for path in BENCH.rglob("*.py"):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            mods = ([a.name for a in node.names] if isinstance(node, ast.Import)
                    else [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
            for mod in mods:
                assert not mod.startswith(("benchmarks", "chip_smoke", "tools")), (path, mod)


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_the_run_refuses_without_a_tpu_and_prints_no_result():
    proc = _run(ROOT, "--workload", "e2-cdp-cnn.full", "--seed", str(2**31 + 5),
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
