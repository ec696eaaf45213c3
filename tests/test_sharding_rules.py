"""Sharding-rule engine tests: spec shapes match param ranks, divisibility
guard works, and a miniature end-to-end lower on a host mesh succeeds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.distributed import sharding as sh
from repro.models import build_model


class FakeMesh:
    """Duck-typed mesh with the production geometry, no devices needed."""
    def __init__(self, shape: dict):
        self._shape = shape

    @property
    def shape(self):
        return self._shape

    @property
    def axis_names(self):
        return tuple(self._shape)


PROD = FakeMesh({"data": 16, "model": 16})


def _leaf_iter(params, specs):
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    return [(sh._path_str(p), leaf, spec)
            for (p, leaf), spec in zip(flat_p, flat_s)]


@pytest.mark.parametrize("arch", ["qwen3_moe_235b_a22b", "mixtral_8x22b",
                                  "falcon_mamba_7b", "zamba2_2_7b",
                                  "whisper_base", "gemma3_4b"])
def test_param_specs_rank_and_divisibility(arch):
    cfg = get_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,),
                                                             jnp.uint32))
    specs = sh.param_specs(shapes, cfg, PROD, fsdp=True)
    for pstr, leaf, spec in _leaf_iter(shapes, specs):
        assert len(spec) <= len(leaf.shape), (pstr, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            total = int(np.prod([PROD.shape[a] for a in axes]))
            assert dim % total == 0, (pstr, spec, leaf.shape)


def test_moe_expert_axis_strategy():
    """E=128 (divisible): expert-parallel; E=8 (mixtral): intra-expert TP."""
    cfg_q = get_config("qwen3_moe_235b_a22b")
    model = build_model(cfg_q)
    shapes = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,),
                                                             jnp.uint32))
    specs = sh.param_specs(shapes, cfg_q, PROD, fsdp=True)
    found = [spec for pstr, leaf, spec in _leaf_iter(shapes, specs)
             if pstr.endswith("moe/w_gate")]
    assert found and all(tuple(s)[1] == "model" for s in found)  # stacked+E

    cfg_m = get_config("mixtral_8x22b")
    model = build_model(cfg_m)
    shapes = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,),
                                                             jnp.uint32))
    specs = sh.param_specs(shapes, cfg_m, PROD, fsdp=True)
    found = [spec for pstr, leaf, spec in _leaf_iter(shapes, specs)
             if pstr.endswith("moe/w_gate")]
    # leading stacked dim None, E=8 not sharded, F on model
    assert found and all(tuple(s)[1] is None and "model" in tuple(s)
                         for s in found)


def test_whisper_vocab_not_sharded():
    cfg = get_config("whisper_base")
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,),
                                                             jnp.uint32))
    specs = sh.param_specs(shapes, cfg, PROD, fsdp=False)
    for pstr, leaf, spec in _leaf_iter(shapes, specs):
        if pstr == "embed/table":
            assert tuple(spec)[0] is None    # 51865 % 16 != 0 -> dropped


def test_needs_fsdp_heuristic():
    assert sh.needs_fsdp(get_config("qwen3_moe_235b_a22b"))
    assert sh.needs_fsdp(get_config("mixtral_8x22b"))
    assert not sh.needs_fsdp(get_config("smollm_360m"))
    assert not sh.needs_fsdp(get_config("qwen3_0_6b"))


def test_batch_specs_modes():
    shape_tr = ShapeConfig("t", 128, 32, "train")
    batch = {"tokens": jax.ShapeDtypeStruct((32, 128), jnp.int32)}
    spec = sh.batch_specs(batch, shape_tr, PROD)
    assert tuple(spec["tokens"])[0] == "data"
    # tiny batch replicates
    batch1 = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    spec1 = sh.batch_specs(batch1, shape_tr, PROD)
    assert tuple(spec1["tokens"])[0] is None


def test_cache_specs_seq_sharding():
    cfg = get_config("qwen3_0_6b")
    model = build_model(cfg)
    shape = ShapeConfig("d", 32768, 128, "decode")
    cache = model.cache_specs(shape)
    specs = sh.cache_specs(cache, shape, PROD)
    k_spec = specs["segments"][0]["0_attn"]["k"]
    assert tuple(k_spec)[1] == "data" and tuple(k_spec)[2] == "model"
    # long-context batch=1: seq over (data, model)
    shape_l = ShapeConfig("l", 524288, 1, "decode")
    cache_l = model.cache_specs(shape_l)
    specs_l = sh.cache_specs(cache_l, shape_l, PROD)
    k_spec_l = specs_l["segments"][0]["0_attn"]["k"]
    assert tuple(k_spec_l)[2] == ("data", "model")


def test_end_to_end_lower_on_host_mesh():
    """Real (1-device) mesh: specs must be accepted by jit and compile."""
    from repro.launch.mesh import make_host_mesh
    from repro.train import optimizer as opt_lib
    from repro.train.trainstep import TrainState, make_train_step
    mesh = make_host_mesh(1, 1)
    cfg = get_smoke_config("smollm_360m")
    model = build_model(cfg)
    opt = opt_lib.sgd()
    with jax.set_mesh(mesh):
        key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
        state_shapes = jax.eval_shape(
            lambda k: TrainState(params=model.init(k),
                                 opt_state=opt.init(model.init(k)),
                                 step=jnp.zeros((), jnp.int32)), key_spec)
        pspecs = sh.param_specs(state_shapes.params, cfg, mesh, fsdp=False)
        sspecs = sh.state_specs(pspecs, state_shapes.opt_state)
        batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
        bspecs = sh.batch_specs(batch, ShapeConfig("t", 32, 2, "train"), mesh)
        step = make_train_step(model, opt)
        jitted = jax.jit(step, in_shardings=(sh.named(mesh, sspecs),
                                             sh.named(mesh, bspecs)))
        compiled = jitted.lower(state_shapes, batch).compile()
        from repro.launch.hlo_analysis import xla_cost_analysis
        assert xla_cost_analysis(compiled).get("flops", 0) > 0
