"""A deployment's own arrival law, reference and traffic driver are new files
that the harness finds by name, and every existing cell takes the built-in
branch at each of the three lookups.

The probe files live in ``probe/``: ``laws/trapezoid.py`` (the simulator's
``TrapezoidWorkload`` and its law in numpy), ``laws/trapezoid_hot.py`` (the
same with a reference rate 10 % high), ``references/trapezoid_slow.py`` (a
reference whose events cost 10 % more work) and
``drivers/train_loop_probe.py`` (a ``TrainLoop`` subclass). They are copied,
with the probe cells' configuration, traffic and limits files, into a copy
of ``bench/`` whose ``BENCHMARK.json`` alone gains entries. Whole harness
runs at N=8 on the CPU, judged as ``test_bench_control.py`` judges them.
"""
import ast
import json
import re
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
PROBE = Path(__file__).resolve().parent / "probe"
sys.path.insert(0, str(BENCH))

import compare  # noqa: E402
import loops  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
from test_bench_control import FLEET_MEDIANS, holds, run_small  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 61
#: the trapezoid law, its peak under the initial levers' batch cap (354,813
#: events a 10.36 s batch), so the arrival rate reaches the service time
TRAPEZOID = {"law": "trapezoid", "base": 2000.0, "peak": 20000.0,
             "ramp_s": 600.0, "plateau_s": 1800.0, "event_size_mb": 0.5}
#: probe cell -> (configuration, its workload law, traffic)
PROBES = {
    "trapezoid.train.jax": ("trapezoid_probe", TRAPEZOID, "train_jax_n1024"),
    "trapezoid_hot.train.jax": ("trapezoid_hot", dict(TRAPEZOID,
                                                      law="trapezoid_hot"),
                                "train_jax_n1024"),
    "trapezoid_slow.train.jax": ("trapezoid_slow", TRAPEZOID,
                                 "train_jax_n1024"),
    "s44.train.probe": ("paper_s44_switching", None, "train_probe_n1024"),
}


def _files(bench: Path) -> dict:
    return {p.relative_to(bench): p.read_bytes() for p in bench.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def probe(tmp_path_factory) -> Path:
    """A copy of ``bench/`` and ``BENCHMARK.json`` with the probe cells
    added as new files and new ``BENCHMARK.json`` entries."""
    root = tmp_path_factory.mktemp("probe")
    b = root / "bench"
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns("__pycache__"))
    for folder in ("laws", "references", "drivers"):
        shutil.copytree(PROBE / folder, b / folder)
    s44 = json.loads((b / "configs" / "paper_s44_switching.json").read_text())
    traffic = json.loads((b / "traffic" / "train_jax_n1024.json").read_text())
    (b / "traffic" / "train_probe_n1024.json").write_text(
        json.dumps(dict(traffic, kind="train_loop_probe")))
    spec = json.loads(json.dumps(SPEC))
    for cell, (config, law, traffic_name) in PROBES.items():
        if law is not None:
            (b / "configs" / f"{config}.json").write_text(
                json.dumps(dict(s44, name=config, workload=law)))
            spec["configs"].append({"name": config, "source": "x",
                                    "file": f"bench/configs/{config}.json",
                                    "reduced": ["initial_levers"],
                                    "why": "x"})
        (b / "limits" / f"{cell}.json").write_text(
            (b / "limits" / "s44.train.jax.json").read_text())
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic_name, "chips": 1,
                                  "why": "x"})
        for m in spec["end_to_end"]:
            if "s44.train.jax" in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def over_limit(checks: dict) -> set:
    """The numbers, fleet medians aside, over their limits."""
    return {k for k, v in checks.items() if k not in FLEET_MEDIANS
            and (v["value"] is None or v["value"] > v["limit"])}


def assert_well_formed(out: dict, cell) -> None:
    line = json.loads(json.dumps(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in cell.e2e} \
        == {"train_windows_per_s", "train_update_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["checks"]) == set(cell.limits)


def test_the_probe_adds_only_new_files(probe):
    """Each probe is new files plus new ``BENCHMARK.json`` entries: every
    file of ``bench/`` is in the copy byte for byte."""
    copied = _files(probe / "bench")
    for rel, data in _files(BENCH).items():
        assert copied[rel] == data, rel
    for cell in PROBES:
        c = run.load_cell(cell, probe)
        assert c.bench == probe / "bench"


def test_a_law_file_runs_and_is_compared(probe, monkeypatch):
    """(a) ``laws/trapezoid.py``: the program runs ``TrapezoidWorkload``
    and the reference states the same law; the run holds."""
    fleet = loops.make_workloads(TRAPEZOID, 3, 7, probe / "bench")
    assert [type(w).__name__ for w in fleet] == ["TrapezoidWorkload"] * 3
    t = np.linspace(0.0, 6000.0, 41)
    want, _ = reference.arrival_law(TRAPEZOID, t, reference.Precision(),
                                    probe / "bench")
    np.testing.assert_allclose(fleet[0].rate(t), want, rtol=1e-12)
    assert want.min() == 2000.0 and want.max() == 20000.0
    cell = "trapezoid.train.jax"
    out = run_small(cell, SEED, monkeypatch, root=probe)
    assert_well_formed(out, run.load_cell(cell, probe))
    assert holds(out["checks"]), out["checks"]


@pytest.mark.parametrize("cell", ["trapezoid_hot.train.jax",
                                  "trapezoid_slow.train.jax"])
def test_a_reference_that_disagrees_reads_not_correct(probe, monkeypatch,
                                                      cell):
    """(b) the law's reference half states a rate 10 % high; (c) the
    configuration's own reference (``references/trapezoid_slow.py``)
    charges 10 % more work an event. Either way the stabilisation wait the
    reference replays is off, so the file is what was compared."""
    c = run.load_cell(cell, probe)
    ref = compare.reference_for(c.deploy, c.bench)
    if cell.startswith("trapezoid_slow"):
        assert ref is not reference
        assert ref.replay_steps.__globals__["service_seconds"] \
            is ref.service_seconds
    else:
        assert ref is reference
    out = run_small(cell, SEED, monkeypatch, root=probe)
    assert out["correct"] is False
    assert "stab_rel_err" in over_limit(out["checks"]), out["checks"]


def test_a_driver_file_runs_to_a_well_formed_line(probe, monkeypatch):
    """(d) a traffic kind that is not built in: ``drivers/<kind>.py``."""
    cell = "s44.train.probe"
    c = run.load_cell(cell, probe)
    kind = c.traffic["kind"]
    assert kind not in loops.DRIVERS
    driver = loops.driver_for(kind, c.bench)
    assert issubclass(driver, loops.TrainLoop) and driver is not loops.TrainLoop
    out = run_small(cell, SEED, monkeypatch, root=probe)
    assert_well_formed(out, c)
    assert holds(out["checks"]), out["checks"]


@pytest.mark.parametrize("lookup", ["program", "switching", "reference",
                                    "driver"])
def test_an_unknown_name_names_the_file_looked_for(lookup):
    """(e) a law or a kind with no file raises, naming the path."""
    law = {"law": "no_such_law"}
    folder = "drivers" if lookup == "driver" else "laws"
    name = "no_such_kind" if lookup == "driver" else "no_such_law"
    path = BENCH / folder / f"{name}.py"
    with pytest.raises(FileNotFoundError, match=re.escape(str(path))):
        if lookup == "program":
            loops.make_workloads(law, 2, 1, BENCH)
        elif lookup == "switching":
            loops.make_workloads({"law": "switching", "period_s": 10.0,
                                  "a": {"law": "poisson", "rate": 1.0,
                                        "event_size_mb": 1.0},
                                  "b": law}, 2, 1, BENCH)
        elif lookup == "reference":
            reference.arrival_law(law, np.zeros(2), reference.Precision(),
                                  BENCH)
        else:
            loops.driver_for(name, BENCH)


def _imports(nodes) -> set:
    out = set()
    for node in nodes:
        if isinstance(node, ast.Import):
            out |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            out.add((node.module or "").split(".")[0])
    return out


def _module_level(tree) -> list:
    """The nodes that run when the module is imported: everything but the
    bodies of functions."""
    out, todo = [], list(tree.body)
    while todo:
        node = todo.pop()
        out.append(node)
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            todo.extend(ast.iter_child_nodes(node))
    return out


@pytest.mark.parametrize("root", [BENCH, PROBE], ids=["bench", "probe"])
def test_laws_and_references_stay_off_the_program(root):
    """(f) no law or reference file imports ``repro`` as it is loaded, and
    no law's ``reference`` half imports it or calls its ``program`` half."""
    files = sorted((root / "laws").glob("*.py")) \
        + sorted((root / "references").glob("*.py"))
    if root == PROBE:
        assert len(files) == 3
    for path in files:
        tree = ast.parse(path.read_text())
        assert "repro" not in _imports(_module_level(tree)), path
        if path.parent.name != "laws":
            continue
        fns = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)}
        body = list(ast.walk(fns["reference"])) if "reference" in fns else []
        assert "repro" not in _imports(body), path
        assert not any(isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                       and n.func.id == "program" for n in body), path


def _laws(law: dict) -> list:
    """A law and the laws it switches between."""
    if law["law"] != "switching":
        return [law]
    return [law] + _laws(law["a"]) + _laws(law["b"])


@pytest.mark.parametrize("cell", CELLS)
def test_existing_cells_take_the_built_in_branches(cell, tmp_path):
    """No move: each existing cell's driver, reference and workload laws
    are the built-in ones, also where a stray file of the same name lies
    under a copy of ``bench/``."""
    c = run.load_cell(cell)
    kind = c.traffic["kind"]
    assert loops.driver_for(kind, c.bench) is loops.DRIVERS[kind]
    assert compare.reference_for(c.deploy, c.bench) is reference

    b = tmp_path / "bench"
    shutil.copytree(BENCH, b, ignore=shutil.ignore_patterns("__pycache__"))
    stray = "raise AssertionError('a stray file took over from a built-in')\n"
    laws = _laws(c.deploy["workload"])
    for law in laws:
        (b / "laws").mkdir(exist_ok=True)
        (b / "laws" / f"{law['law']}.py").write_text(stray)
    (b / "drivers").mkdir()
    (b / "drivers" / f"{kind}.py").write_text(stray)
    assert loops.driver_for(kind, b) is loops.DRIVERS[kind]

    fleet = loops.make_workloads(c.deploy["workload"], 4, SEED, b)
    assert len(fleet) == 4
    assert {type(w).__module__ for w in fleet} == {"repro.data.workloads"}
    t = np.linspace(0.0, 7200.0, 25)
    q = reference.Precision()
    for law in laws:
        got = reference.arrival_law(law, t, q, b)
        want = reference.arrival_law(law, t, q, BENCH)
        np.testing.assert_array_equal(got, want)
