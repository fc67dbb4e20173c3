"""Every cell resolves to its files by name, and each traffic driver runs a
whole benchmark run at N=8 through the harness's own functions, on the CPU
(the harness's look for a chip skipped), ending in a well-formed line."""
import json
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

import loops  # noqa: E402
import run  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SMALL = {"fleet": 8, "warmup_units": 0}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = run.load_cell(cell)
    assert c.traffic["kind"] in loops.DRIVERS
    assert (BENCH / "limits" / f"{cell}.json").exists()
    assert c.deploy["name"] == c.cell["config"]
    assert {m["name"] for m in c.e2e} >= {"setup_s"}
    assert len(c.e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(run.metric_reader(BENCH, m["name"]))
        assert m["moves"] in {e["name"] for e in c.e2e}
    cfg = next(x for x in SPEC["configs"] if x["name"] == c.cell["config"])
    assert cfg["file"].startswith("bench/configs/")
    assert sorted(c.deploy["reduced"]) == sorted(cfg["reduced"])


def test_every_config_and_metric_is_used():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in SPEC["per_layer"] + SPEC["end_to_end"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_one_run_at_n8_ends_in_a_well_formed_line(cell):
    import jax

    c = run.load_cell(cell)
    c.traffic.update(SMALL)
    args = SimpleNamespace(workload=cell, seed=2**31 + 977, seconds=0.01,
                           trace=0)
    out = run.measure(c, args, jax.devices()[:1], time.perf_counter())
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in c.e2e}
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["checks"]) == set(c.limits)
    for v in line["checks"].values():
        assert set(v) == {"value", "limit"}


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files plus new ``BENCHMARK.json`` entries resolve by name; no file the
    benchmark already has is edited."""
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench")
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    b = tmp_path / "bench"
    deploy = json.loads((b / "configs" / "ysb_ads.json").read_text())
    (b / "configs" / "ysb_ads_copy.json").write_text(
        json.dumps(dict(deploy, name="ysb_ads_copy")))
    traffic = json.loads((b / "traffic" / "train_jax_n1024.json").read_text())
    (b / "traffic" / "train_jax_n256.json").write_text(
        json.dumps(dict(traffic, fleet=256)))
    (b / "limits" / "ysb.train.jax.json").write_text(
        (b / "limits" / "s44.train.jax.json").read_text())
    (b / "metrics" / "host_share.train.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "ysb_ads_copy", "source": "x",
                            "file": "bench/configs/ysb_ads_copy.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "ysb.train.jax", "config": "ysb_ads_copy",
                              "traffic": "train_jax_n256", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if "s44.train.jax" in m.get("workloads", []):
            m["workloads"].append("ysb.train.jax")
    spec["per_layer"].append({"name": "host_share.train", "unit": "%",
                              "better": "lower", "source": "device_trace",
                              "layer": "host between programs",
                              "moves": "train_windows_per_s",
                              "workloads": ["ysb.train.jax"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    c = run.load_cell("ysb.train.jax", tmp_path)
    assert c.deploy["name"] == "ysb_ads_copy" and c.traffic["fleet"] == 256
    assert {m["name"] for m in c.e2e} == {"train_windows_per_s",
                                          "train_update_p95_ms", "setup_s"}
    assert [m["name"] for m in c.per_layer] == ["host_share.train"]
    assert run.metric_reader(c.bench, "host_share.train")(None) == 1.0
    assert all(p.read_bytes() == v for p, v in before.items())


def test_refuses_to_run_without_a_tpu(tmp_path):
    """Off-TPU the command exits non-zero and prints no result; so it does
    in a directory holding only BENCHMARK.json and the benchmark's files."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    cmd = [sys.executable] + SPEC["command"][1:] + [
        "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0"]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in SPEC["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d)
    p = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                       env=env, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
