"""Compile the FL data-plane kernels for a described TPU v5e chip.

Interpret mode runs a kernel body but never asks the TPU compiler whether
it accepts the kernel's tiling, VMEM use or lowering.  These tests compile
each kernel of the main path (``kernels/diffusion.py``, ``kernels/quant.py``)
at real widths for one chip of a ``v5e:2x2`` topology that is described,
not attached, and check that the Pallas kernel survives into the program
(``tpu_custom_call``).  Nothing runs, so nothing is timed.

Widths: the paper's FMNIST-shaped CNN fleet (C=20, F=206,874), a C=2048
fleet of the LoRA LM task's adapters (F=3,584), the planner's bid matrices
for both fleets, and the full smollm_360m parameter stack of two clients
(the trainer's fleet: F=361,821,120, 1,413,364 int8 row-blocks).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.diffusion import (bid_value_fuse_pallas,
                                     dol_bid_scores_pallas,
                                     mix_aggregate_pallas, stc_rows_pallas)
from repro.kernels.quant import (QUANT_BLOCK, quant_pack_pallas,
                                 quant_unpack_pallas)

CNN_F = 206_874          # fl.models "cnn" at dim=784, 10 classes
LM_ADAPTER_F = 3_584     # fl.models "lm" LoRA adapter
SMOLLM_F = 361_821_120   # configs.smollm_360m, as models.build_model inits it
CLASSES = 10

# (clients, flat params per client) of each fleet the kernels serve
FLEETS = {"cnn_c20": (20, CNN_F), "lm_c2048": (2048, LM_ADAPTER_F),
          "smollm_c2": (2, SMOLLM_F)}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32, I8, BOOL = jnp.float32, jnp.int8, jnp.bool_


@pytest.mark.parametrize("fleet,g", [("cnn_c20", "C"), ("cnn_c20", 1),
                                     ("lm_c2048", "C"), ("lm_c2048", 1),
                                     ("smollm_c2", 1)])
def test_mix_aggregate_compiles(one_chip, fleet, g):
    c, f = FLEETS[fleet]
    g = c if g == "C" else g
    _compile(mix_aggregate_pallas, one_chip, ((c, f), F32), ((g, c), F32))


@pytest.mark.parametrize("fleet", ["cnn_c20", "lm_c2048"])
def test_stc_topk_compiles(one_chip, fleet):
    c, n = FLEETS[fleet]
    n = min(n, 100_000)      # the largest leaf an STC hop compresses
    _compile(lambda x, r, m: stc_rows_pallas(x, r, m, 0.01), one_chip,
             ((c, n), F32), ((n,), F32), ((c,), BOOL))


@pytest.mark.parametrize("fleet", ["cnn_c20", "lm_c2048"])
def test_dol_bid_scores_compiles(one_chip, fleet):
    n = FLEETS[fleet][0]
    _compile(dol_bid_scores_pallas, one_chip, ((n, CLASSES), F32),
             ((n,), F32), ((n, CLASSES), F32), ((n,), F32))


@pytest.mark.parametrize("fleet", ["cnn_c20", "lm_c2048"])
def test_bid_value_fuse_compiles(one_chip, fleet):
    n = FLEETS[fleet][0]
    _compile(lambda b, v: bid_value_fuse_pallas(b, v, 0.5), one_chip,
             ((n, n), F32), ((n,), F32))


def _quant_rows(fleet: str) -> int:
    c, f = FLEETS[fleet]
    return c * -(-f // QUANT_BLOCK)


# The smollm stack's rows end in a ragged 256-row tile; 57 rows are one
# tile as tall as the array, not a multiple of the int8 sublane tile.
QUANT_ROWS = pytest.mark.parametrize(
    "r", [_quant_rows("lm_c2048"), _quant_rows("smollm_c2"), 57],
    ids=["lm_c2048", "smollm_c2", "r57"])


@QUANT_ROWS
def test_quant_pack_compiles(one_chip, r):
    _compile(quant_pack_pallas, one_chip, ((r, QUANT_BLOCK), F32))


@QUANT_ROWS
def test_quant_unpack_compiles(one_chip, r):
    _compile(quant_unpack_pallas, one_chip, ((r, QUANT_BLOCK), I8),
             ((r,), F32))
