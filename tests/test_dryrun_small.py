"""Dry-run machinery on a small forced-multi-device mesh (subprocess: the
512-device production dry-run is exercised by ``python -m repro.launch.dryrun``;
here an 8-device host proves the same code path: lower + compile + roofline
extraction + split-K decode, in seconds)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import numpy as np
from repro import configs
from repro.configs.base import InputShape
from repro.distribution.steps import make_step_for_cell
from repro.launch.dryrun import collective_bytes_from_hlo, roofline_terms

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
out = {}
cells = [
    ("smollm_135m", InputShape("t", 128, 8, "train")),
    ("qwen2_moe_a2p7b", InputShape("p", 128, 4, "prefill")),
    ("zamba2_2p7b", InputShape("d", 256, 1, "decode")),  # batch 1 -> split-K
    ("rwkv6_7b", InputShape("d", 256, 8, "decode")),
]
for arch, shape in cells:
    cfg = configs.get(arch, reduced=True)
    with mesh:
        bundle = make_step_for_cell(cfg, mesh, shape)
        compiled = bundle.lower().compile()
        hlo = compiled.as_text()
        cost = compiled.cost_analysis()
        coll = collective_bytes_from_hlo(hlo)
        terms = roofline_terms(float(cost.get("flops", 0.0)),
                               float(cost.get("bytes accessed", 0.0)), coll, 8)
    out[arch] = {
        "collective_bytes": coll,
        "dominant": terms["dominant"],
        "split_k": bundle.meta.get("split_k", False),
        "mem": compiled.memory_analysis().temp_size_in_bytes,
    }
print("JSON" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dryrun_output():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    payload = r.stdout.split("JSON", 1)[1]
    return json.loads(payload)


def test_all_small_cells_compile(dryrun_output):
    assert set(dryrun_output) == {"smollm_135m", "qwen2_moe_a2p7b",
                                  "zamba2_2p7b", "rwkv6_7b"}


def test_train_cell_has_gradient_collectives(dryrun_output):
    coll = dryrun_output["smollm_135m"]["collective_bytes"]
    moved = sum(v for k, v in coll.items() if k != "counts")
    assert moved > 0, coll  # DP grads + TP activations must move bytes


def test_long_context_decode_uses_split_k(dryrun_output):
    assert dryrun_output["zamba2_2p7b"]["split_k"] is True
    assert dryrun_output["rwkv6_7b"]["split_k"] is False


def test_roofline_terms_have_a_dominant(dryrun_output):
    for arch, rec in dryrun_output.items():
        assert rec["dominant"] in ("compute", "memory", "collective")


def test_collective_parser_on_synthetic_hlo():
    from repro.launch.dryrun import collective_bytes_from_hlo

    hlo = """
  %ag = bf16[8,256]{1,0} all-gather(%x), replica_groups={{0,1}}, dimensions={0}
  ROOT %ar = f32[128]{0} all-reduce(%y), to_apply=%sum
  %rs = (f32[64]{0}, f32[64]{0}) reduce-scatter(%a, %b), dimensions={0}
  %noise = f32[2]{0} add(%p, %q)
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-gather"] == 8 * 256 * 2
    assert out["all-reduce"] == 128 * 4
    assert out["reduce-scatter"] == 2 * 64 * 4
    assert out["counts"]["all-gather"] == 1
