"""Distribution layer: padding, pspec rules, dp-axis selection (property)."""
import jax
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep; skip, never fail collection
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distribution import sharding as sh
from repro.models import lm


def _mesh(shape=(1, 1)):
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_meshspec_detects_axes():
    m = _mesh()
    ms = sh.MeshSpec.for_mesh(m)
    assert ms.data == ("data",) and ms.model == "model"


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 512))
def test_dp_axes_product_divides_batch(batch):
    m = _mesh()
    ms = sh.MeshSpec(data=("pod", "data"))

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    axes = sh.dp_axes_for(batch, FakeMesh(), ms)
    prod = int(np.prod([FakeMesh.shape[a] for a in axes])) if axes else 1
    assert batch % prod == 0
    # maximality: adding the next axis to the left must not divide
    remaining = [a for a in ("pod", "data") if a not in axes]
    if remaining and axes != ("pod", "data"):
        bigger = prod * FakeMesh.shape[remaining[-1]]
        assert batch % bigger != 0 or axes == ()


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_pad_config_divisibility_tp16(arch):
    cfg = configs.get(arch)
    p = sh.pad_config_for_mesh(cfg, 16)
    if cfg.family != "ssm":
        assert p.num_kv_heads % 16 == 0 or p.num_heads % 16 == 0
        assert p.num_heads % max(p.num_kv_heads, 1) == 0  # GQA grouping intact
    assert p.vocab_size % 16 == 0
    if p.vocab_size != cfg.vocab_size:
        assert p.vocab_true == cfg.vocab_size


@pytest.mark.parametrize("arch", ["qwen2_7b", "qwen2_moe_a2p7b", "rwkv6_7b",
                                  "zamba2_2p7b", "whisper_large_v3"])
def test_param_pspecs_cover_every_large_leaf(arch):
    cfg = sh.pad_config_for_mesh(configs.get(arch), 16)
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0),
                                                   max_seq=4096))
    ms = sh.MeshSpec()
    specs = sh.param_pspecs(cfg, shapes, ms)  # raises if a big leaf is unruled
    flat_shapes = jax.tree_util.tree_leaves(shapes)
    flat_specs = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for shp, spec in zip(flat_shapes, flat_specs):
        assert len(spec) == len(shp.shape), (shp.shape, spec)


def test_param_pspecs_raises_on_unruled_large_leaf():
    cfg = configs.get("smollm_135m")
    fake = {"mystery_big": jax.ShapeDtypeStruct((2048, 2048), jax.numpy.float32)}
    with pytest.raises(ValueError, match="no sharding rule"):
        sh.param_pspecs(cfg, fake, sh.MeshSpec())


def test_make_shard_fn_skips_nondivisible_axes():
    m = _mesh((1, 1))
    ms = sh.MeshSpec.for_mesh(m)
    shard = sh.make_shard_fn(m, ms, ("data",))
    x = jax.numpy.ones((3, 5, 7))  # nothing divides -> constraint must no-op
    y = shard("act_ff", x)
    assert y.shape == x.shape


def test_state_pspecs_split_k_shards_sequence():
    cfg = sh.pad_config_for_mesh(configs.get("zamba2_2p7b"), 16)
    state_shape = jax.eval_shape(lambda: lm.init_decode_state(cfg, 1, 1024))
    ms = sh.MeshSpec()
    specs = sh.state_pspecs(cfg, state_shape, ms, ("data",), shard_kv_seq=True)
    assert specs.kv_k[2] == ("data",) or specs.kv_k[2] == "data"
    specs2 = sh.state_pspecs(cfg, state_shape, ms, ("data",), shard_kv_seq=False)
    assert specs2.kv_k[1] == ("data",) or specs2.kv_k[1] == "data"


def test_padding_flops_ratio_below_one_when_padded():
    cfg = configs.get("qwen2_7b")
    p = sh.pad_config_for_mesh(cfg, 16)
    r = sh.padding_flops_ratio(cfg, p)
    assert 0.5 < r < 1.0  # 28->32 heads + vocab pad wastes some compute
