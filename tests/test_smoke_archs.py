"""Per-architecture smoke tests: reduced same-family configs, one forward +
one train step on CPU, asserting output shapes and no NaNs (deliverable f).
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import build_model
from repro.train import init_train_state, make_train_step, sgd

B, S = 2, 32


def _batch(cfg, key):
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.frontend == "vision":
        batch["patch_embeddings"] = jax.random.normal(
            key, (B, cfg.num_frontend_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.frontend == "audio":
        batch["frames"] = jax.random.normal(
            key, (B, cfg.num_frontend_tokens, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_reduced_config_bounds(arch):
    cfg = get_smoke_config(arch)
    assert cfg.num_layers <= 4 and cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    opt = sgd()
    state = init_train_state(model, key, opt)
    batch = _batch(cfg, key)
    step = jax.jit(make_train_step(model, opt))
    state, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert jnp.isfinite(loss), f"{arch} produced non-finite loss"
    # a random model on a uniform-ish vocab should start near ln(V)
    assert 0.5 * jnp.log(cfg.vocab_size) < loss < 2.5 * jnp.log(cfg.vocab_size)
    for leaf in jax.tree.leaves(state.params):
        assert jnp.isfinite(leaf).all(), f"{arch} param NaN after step"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    max_seq = 64
    if cfg.family == "audio":
        frames = jax.random.normal(
            key, (B, cfg.num_frontend_tokens, cfg.d_model), jnp.bfloat16)
        cache = model.init_cache(params, frames, B, max_seq)
    else:
        cache = model.init_cache(params, B, max_seq)
    tok = jnp.ones((B, 1), jnp.int32)
    logits, cache2 = model.decode_step(params, tok, cache, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    logits3, _ = model.decode_step(params, tok, cache2, jnp.int32(1))
    assert jnp.isfinite(logits3).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_configs_match_assignment(arch):
    """The FULL configs carry the exact assigned geometry (exercised only
    via the dry-run — never instantiated here)."""
    cfg = get_config(arch)
    expected = {
        "qwen3_moe_235b_a22b": (94, 4096, 64, 4, 151936),
        "moonshot_v1_16b_a3b": (27, 2048, 16, 16, 163840),
        "gemma3_4b": (34, 2560, 8, 4, 262144),
        "mixtral_8x22b": (56, 6144, 48, 8, 32768),
        "smollm_360m": (32, 960, 15, 5, 49152),
        "pixtral_12b": (40, 5120, 32, 8, 131072),
        "qwen3_0_6b": (28, 1024, 16, 8, 151936),
        "whisper_base": (6, 512, 8, 8, 51865),
        "zamba2_2_7b": (54, 2560, 32, 32, 32000),
        "falcon_mamba_7b": (64, 4096, 1, 1, 65024),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.vocab_size)
    assert got == expected
    if arch == "qwen3_moe_235b_a22b":
        assert (cfg.moe.num_experts, cfg.moe.top_k,
                cfg.moe.d_ff_expert) == (128, 8, 1536)
    if arch == "moonshot_v1_16b_a3b":
        assert (cfg.moe.num_experts, cfg.moe.top_k,
                cfg.moe.d_ff_expert) == (64, 6, 1408)
        assert (cfg.first_k_dense, cfg.d_ff, cfg.moe.scoring,
                cfg.moe.num_shared_experts) == (1, 11264, "sigmoid", 2)
        assert cfg.mla.kv_lora_rank == 512
    if arch == "mixtral_8x22b":
        assert (cfg.moe.num_experts, cfg.moe.top_k,
                cfg.moe.d_ff_expert) == (8, 2, 16384)
    if arch == "zamba2_2_7b":
        assert cfg.ssm.d_state == 64
    if arch == "falcon_mamba_7b":
        assert cfg.ssm.d_state == 16 and cfg.d_ff == 0
    if arch == "gemma3_4b":
        assert cfg.local_global_ratio == 5 and cfg.d_ff == 10240


@pytest.mark.parametrize("arch", ["smollm_360m", "falcon_mamba_7b",
                                  "whisper_base"])
def test_input_specs_shapes(arch):
    from repro.configs import SHAPES
    cfg = get_config(arch)
    model = build_model(cfg)
    for name, shp in SHAPES.items():
        specs = model.input_specs(shp)
        if shp.mode == "decode":
            assert specs["tokens"].shape == (shp.global_batch, 1)
        else:
            assert specs["tokens"].shape == (shp.global_batch, shp.seq_len)
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)
