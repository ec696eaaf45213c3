"""FL diffusion data-plane kernels (kernels/diffusion.py): reference parity
in pallas_interpret and ref modes, dispatch plumbing, and end-to-end
executor/planner parity with the kernels forced on."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.diffusion import (dol_bid_scores_pallas,
                                     dol_bid_scores_xla_fused,
                                     mix_aggregate_pallas, stc_rows_pallas)

RNG = np.random.default_rng(7)


# ------------------------------------------------------------ mix_aggregate

@pytest.mark.parametrize("c,f,g", [
    (8, 1000, 8),        # MixOp: full (C, C) mixing matrix
    (8, 1000, 1),        # Eq.-11 aggregation row
    (20, 257, 20),       # F not lane-aligned
    (5, 64, 3),          # sharded partial: G != C, tiny F
    (64, 50890, 64),     # fcn-sized flattened fleet
])
def test_mix_aggregate_matches_ref(c, f, g):
    x = jnp.asarray(RNG.normal(size=(c, f)), jnp.float32)
    w = jnp.asarray(RNG.random(size=(g, c)), jnp.float32)
    out = ops.mix_aggregate(x, w, implementation="pallas_interpret")
    want = ops.mix_aggregate(x, w, implementation="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_mix_aggregate_client_tiled_accumulate_matches_ref():
    """C > block_c streams client tiles through the revolving accumulator;
    the result must match the single-slab product (and C % block_c != 0
    must be handled by zero padding)."""
    x = jnp.asarray(RNG.normal(size=(90, 700)), jnp.float32)
    w = jnp.asarray(RNG.random(size=(90, 90)), jnp.float32)
    out = mix_aggregate_pallas(x, w, block_c=32, interpret=True)
    want = jnp.einsum("gc,cf->gf", w, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("g", [300, 1, 33])
def test_mix_aggregate_row_tiled_matches_ref(g):
    """G > BLOCK_G tiles the output rows (a MixOp of a large fleet); the
    ragged last row and feature tiles must not leak into the result."""
    x = jnp.asarray(RNG.normal(size=(90, 700)), jnp.float32)
    w = jnp.asarray(RNG.random(size=(g, 90)), jnp.float32)
    out = mix_aggregate_pallas(x, w, block_c=32, block_f=256, interpret=True)
    want = jnp.einsum("gc,cf->gf", w, x)
    assert out.shape == (g, 700)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_mix_aggregate_tree_paths_agree():
    """Tree-level dispatch: the per-leaf XLA chain and the flattened Pallas
    pass compute the same mix and the same (squeezed) aggregate."""
    params = {"w": jnp.asarray(RNG.normal(size=(6, 17, 3)), jnp.float32),
              "b": jnp.asarray(RNG.normal(size=(6, 9)), jnp.float32)}
    w_mix = jnp.asarray(RNG.random(size=(6, 6)), jnp.float32)
    w_agg = jnp.asarray(RNG.random(size=(1, 6)), jnp.float32)
    for w, collapse in ((w_mix, False), (w_agg, True)):
        a = ops.mix_aggregate_tree(params, w, collapse=collapse,
                                   implementation="ref")
        b = ops.mix_aggregate_tree(params, w, collapse=collapse,
                                   implementation="pallas_interpret")
        for la, lb, orig in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                                jax.tree.leaves(params)):
            want_shape = (orig.shape[1:] if collapse
                          else (w.shape[0],) + orig.shape[1:])
            assert la.shape == lb.shape == want_shape
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=1e-5, rtol=1e-5)


def test_mix_aggregate_tree_one_slot_mix_stays_stacked():
    """A legitimate one-slot MixOp has w (1, 1) — without collapse the
    client axis must survive on both paths."""
    params = {"w": jnp.asarray(RNG.normal(size=(1, 4, 3)), jnp.float32)}
    w = jnp.ones((1, 1), jnp.float32)
    for impl in ("ref", "pallas_interpret"):
        out = ops.mix_aggregate_tree(params, w, implementation=impl)
        assert out["w"].shape == (1, 4, 3), impl
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(params["w"]), atol=1e-6)


def test_mix_aggregate_ref_is_flat_einsum():
    x = jnp.asarray(RNG.normal(size=(6, 100)), jnp.float32)
    w = jnp.asarray(RNG.random(size=(6, 6)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ops.mix_aggregate(x, w, implementation="ref")),
        np.asarray(jnp.einsum("gc,cf->gf", w, x)))


# ------------------------------------------------------------------ stc_topk

@pytest.mark.parametrize("c,n,sparsity", [
    (6, 530, 0.05),
    (4, 4096, 0.01),
    (10, 64, 0.1),       # n below one lane tile
    (3, 10000, 0.001),
    (20, 9000, 0.01),    # C pads 20 -> 24 rows, n spans two feature tiles
    (3, 8192 + 7, 0.01),  # C below one row tile, ragged last feature tile
])
def test_stc_topk_matches_ref(c, n, sparsity):
    x = jnp.asarray(RNG.normal(size=(c, n)), jnp.float32)
    r = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    mask = jnp.asarray(RNG.random(c) < 0.6)
    out = ops.stc_topk(x, r, mask, sparsity,
                       implementation="pallas_interpret")
    want = ops.stc_topk(x, r, mask, sparsity, implementation="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-6)


def test_stc_topk_unmasked_rows_bit_identical():
    x = jnp.asarray(RNG.normal(size=(5, 300)), jnp.float32)
    r = jnp.asarray(RNG.normal(size=(300,)), jnp.float32)
    mask = jnp.asarray([False, True, False, True, False])
    out = stc_rows_pallas(x, r, mask, 0.05, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[~np.asarray(mask)]),
                                  np.asarray(x[~np.asarray(mask)]))


def test_stc_topk_sparsity_level():
    x = jnp.asarray(RNG.normal(size=(3, 2048)), jnp.float32)
    r = jnp.zeros((2048,), jnp.float32)
    mask = jnp.ones((3,), bool)
    out = ops.stc_topk(x, r, mask, 0.01, implementation="pallas_interpret")
    for row in np.asarray(out):
        assert int((row != 0).sum()) == max(1, int(2048 * 0.01))


def test_masked_stc_compress_routes_through_ops(monkeypatch):
    """fedshard's hop compression gives the same payload on both paths."""
    from repro.distributed.fedshard import masked_stc_compress
    params = {"w": jnp.asarray(RNG.normal(size=(4, 17, 3)), jnp.float32),
              "b": jnp.asarray(RNG.normal(size=(4, 9)), jnp.float32)}
    refp = {"w": jnp.asarray(RNG.normal(size=(17, 3)), jnp.float32),
            "b": jnp.asarray(RNG.normal(size=(9,)), jnp.float32)}
    mask = jnp.asarray([True, False, True, True])
    host = masked_stc_compress(params, refp, mask, 0.1,
                               implementation="ref")
    kern = masked_stc_compress(params, refp, mask, 0.1,
                               implementation="pallas_interpret")
    for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(kern)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# ------------------------------------------------------------ dol_bid_scores

def _planner_inputs(m, n, c, zero_rows=True):
    dol = jnp.asarray(RNG.dirichlet(np.ones(c), size=m), jnp.float32)
    chain = jnp.asarray(RNG.integers(1, 500, size=m), jnp.float32)
    if zero_rows:   # never-trained model: dol = 0, chain = 0
        dol = dol.at[0].set(0.0)
        chain = chain.at[0].set(0.0)
    dsi = jnp.asarray(RNG.dirichlet(np.ones(c), size=n), jnp.float32)
    sizes = jnp.asarray(RNG.integers(0, 300, size=n), jnp.float32)
    return dol, chain, dsi, sizes


@pytest.mark.parametrize("m,n,c", [(4, 10, 10), (16, 130, 5), (64, 256, 10)])
def test_dol_bid_scores_matches_composite(m, n, c):
    dol, chain, dsi, sizes = _planner_inputs(m, n, c)
    want = ops.dol_bid_scores(dol, chain, dsi, sizes, implementation="ref")
    fused = dol_bid_scores_xla_fused(dol, chain, dsi, sizes)
    out = ops.dol_bid_scores(dol, chain, dsi, sizes,
                             implementation="pallas_interpret")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_dol_bid_scores_near_uniform_no_cancellation():
    """As DoLs converge to uniform (dist → 0) the centered expansion must
    not lose precision — the regime every diffusion round ends in."""
    m, n, c = 8, 12, 10
    dol = jnp.full((m, c), 1.0 / c) + jnp.asarray(
        RNG.normal(size=(m, c)) * 1e-4, jnp.float32)
    dol = dol / dol.sum(axis=1, keepdims=True)
    chain = jnp.asarray(RNG.integers(100, 500, size=m), jnp.float32)
    dsi = jnp.full((n, c), 1.0 / c, jnp.float32)
    sizes = jnp.asarray(RNG.integers(50, 100, size=n), jnp.float32)
    want = ops.dol_bid_scores(dol, chain, dsi, sizes, implementation="ref")
    out = ops.dol_bid_scores(dol, chain, dsi, sizes,
                             implementation="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-7)


def test_dol_bid_scores_non_default_metric_falls_back():
    dol, chain, dsi, sizes = _planner_inputs(4, 8, 6)
    for metric in ("kld", "jsd", "w1_true"):
        out = ops.dol_bid_scores(dol, chain, dsi, sizes, metric=metric,
                                 implementation="pallas_interpret")
        want = ref.dol_bid_scores_ref(dol, chain, dsi, sizes, metric)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_dol_bid_scores_vmaps():
    """plan_rounds_batched vmaps the planner over sweep cells — the kernel
    must batch."""
    dols, chains, dsis, sizess = [], [], [], []
    for _ in range(3):
        d, ch, ds, sz = _planner_inputs(4, 10, 10)
        dols.append(d), chains.append(ch), dsis.append(ds), sizess.append(sz)
    stack = map(jnp.stack, (dols, chains, dsis, sizess))
    out = jax.vmap(lambda d, ch, ds, sz: dol_bid_scores_pallas(
        d, ch, ds, sz, interpret=True))(*stack)
    for i in range(3):
        want = ref.dol_bid_scores_ref(dols[i], chains[i], dsis[i],
                                      sizess[i])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want),
                                   atol=2e-5)


# ------------------------------------------------------------- dispatch

@pytest.mark.parametrize("call", [
    lambda: ops.mix_aggregate(jnp.ones((4, 8)), jnp.ones((1, 4)),
                              implementation="pallas"),
    lambda: ops.stc_topk(jnp.ones((4, 8)), jnp.ones((8,)),
                         jnp.ones((4,), bool), implementation="pallas"),
    lambda: ops.dol_bid_scores(*_planner_inputs(4, 8, 6),
                               implementation="pallas"),
    lambda: ops.bid_value_fuse(jnp.ones((4, 8)), jnp.ones((8,)), 0.5,
                               implementation="pallas"),
    lambda: ops.quant_pack(jnp.ones((4, 512)), implementation="pallas"),
    lambda: ops.quant_unpack(jnp.ones((4, 512), jnp.int8), jnp.ones((4,)),
                             implementation="pallas"),
], ids=["mix_aggregate", "stc_topk", "dol_bid_scores", "bid_value_fuse",
        "quant_pack", "quant_unpack"])
def test_explicit_pallas_off_tpu_raises(monkeypatch, call):
    """An explicit "pallas" off a TPU raises instead of quietly running the
    interpreter, so no CPU run can pass for a compiled-kernel run."""
    monkeypatch.delenv("REPRO_KERNELS_IMPL", raising=False)
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="pallas_interpret"):
        call()


def test_resolve_accepts_ref_alias(monkeypatch):
    assert ops._resolve("ref") == "xla"
    monkeypatch.setenv("REPRO_KERNELS_IMPL", "ref")
    assert ops._resolve("auto") == "xla"
    monkeypatch.setenv("REPRO_KERNELS_IMPL", "pallas_interpret")
    assert ops._resolve("auto") == "pallas_interpret"
    assert ops._resolve("ref") == "xla"      # explicit arg beats env


# ----------------------------------------------- end-to-end kernel parity

def _spec(strategy, executor, clients=4, rounds=2):
    from repro.fl import ExperimentSpec, FLConfig
    return ExperimentSpec(
        task="fcn", alpha=0.3, num_samples=800,
        fl=FLConfig(strategy=strategy, rounds=rounds, num_clients=clients,
                    num_models=clients, seed=0, topology_seed=3,
                    engine=executor, tthf_cluster_size=2,
                    tthf_global_period=2))


@pytest.mark.parametrize("strategy", ["gossip", "feddif_stc", "tthf"])
def test_fleet_kernel_data_plane_parity(monkeypatch, strategy):
    """Host executor (pure reference) vs fleet executor with every data-
    plane op forced onto the interpreted Pallas kernels: ledgers identical,
    params within the executor-parity tolerance."""
    from repro.fl import run_experiment
    monkeypatch.delenv("REPRO_KERNELS_IMPL", raising=False)
    host = run_experiment(_spec(strategy, "host"))
    monkeypatch.setenv("REPRO_KERNELS_IMPL", "pallas_interpret")
    fleet = run_experiment(_spec(strategy, "fleet"))
    assert host.ledger.as_dict() == fleet.ledger.as_dict()
    for a, b in zip(jax.tree.leaves(host.final_params),
                    jax.tree.leaves(fleet.final_params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-4, rtol=2e-3)


def test_planner_bids_kernel_inside_while_loop(monkeypatch):
    """The jitted round loop (lax.while_loop) with the Pallas bid kernel
    produces the same plan tensors as the reference composite."""
    from repro.core.planner import _plan_rounds, PlanInputs
    m, n, c, r = 3, 6, 5, 4
    dol = jnp.asarray(RNG.dirichlet(np.ones(c), size=m), jnp.float32)
    inp = PlanInputs(
        dol0=dol,
        chain_size0=jnp.asarray(RNG.integers(50, 200, size=m), jnp.float32),
        visited0=jnp.zeros((m, n), bool),
        holder0=jnp.arange(m, dtype=jnp.int32),
        dsi=jnp.asarray(RNG.dirichlet(np.ones(c), size=n), jnp.float32),
        data_sizes=jnp.asarray(RNG.integers(50, 200, size=n), jnp.float32),
        gamma_seq=jnp.asarray(1.0 + RNG.random((r, n, n)), jnp.float32),
        mean_snr=jnp.asarray(10.0 * jnp.ones((n, n)), jnp.float32),
        epsilon=jnp.float32(0.01),
        gamma_min=jnp.float32(0.5),
        outage_max=jnp.float32(0.9),
        bandwidth_budget=jnp.float32(1e9),
        model_bits=jnp.float32(1e5))
    monkeypatch.delenv("REPRO_KERNELS_IMPL", raising=False)
    want = _plan_rounds(inp, metric="w1_norm", allow_retraining=False)
    monkeypatch.setenv("REPRO_KERNELS_IMPL", "pallas_interpret")
    out = _plan_rounds(inp, metric="w1_norm", allow_retraining=False)
    assert int(out.num_rounds) == int(want.num_rounds)
    np.testing.assert_array_equal(np.asarray(out.scheduled),
                                  np.asarray(want.scheduled))
    np.testing.assert_array_equal(np.asarray(out.dst),
                                  np.asarray(want.dst))
    np.testing.assert_allclose(np.asarray(out.weight),
                               np.asarray(want.weight), atol=1e-4)


# ----------------------------------------------------- bid_value_fuse trio

@pytest.mark.parametrize("m,n", [(3, 5), (16, 20), (130, 257)])
def test_bid_value_fuse_pallas_matches_ref(m, n):
    rng = np.random.default_rng(0)
    bids = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    value = jnp.asarray(rng.uniform(size=n), jnp.float32)
    want = ref.bid_value_fuse_ref(bids, value, 0.7)
    from repro.kernels.diffusion import bid_value_fuse_pallas
    got = bid_value_fuse_pallas(bids, value, 0.7, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_bid_value_fuse_weight_zero_is_identity():
    rng = np.random.default_rng(1)
    bids = jnp.asarray(rng.normal(size=(8, 12)), jnp.float32)
    value = jnp.asarray(rng.uniform(size=12), jnp.float32)
    out = ops.bid_value_fuse(bids, value, 0.0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(bids))


def test_bid_value_fuse_ops_dispatch_routes_both_impls():
    rng = np.random.default_rng(2)
    bids = jnp.asarray(rng.normal(size=(6, 9)), jnp.float32)
    value = jnp.asarray(rng.uniform(size=9), jnp.float32)
    a = ops.bid_value_fuse(bids, value, 1.3, implementation="xla")
    b = ops.bid_value_fuse(bids, value, 1.3,
                           implementation="pallas_interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)
    # fused sign structure: value in [0,1], w > -1 preserves bid signs
    assert (np.sign(np.asarray(a)) == np.sign(np.asarray(bids))).all()


def test_bid_value_fuse_host_oracle_agrees():
    """The host auction's fusion (numpy) and the kernel trio agree — the
    planner-mode parity the scenario sweeps rely on."""
    from repro.core.auction import fuse_learning_value
    rng = np.random.default_rng(3)
    bids = rng.normal(size=(5, 7))
    value = rng.uniform(size=7)
    host = fuse_learning_value(bids, value, 0.4)
    dev = ops.bid_value_fuse(jnp.asarray(bids, jnp.float32),
                             jnp.asarray(value, jnp.float32), 0.4)
    np.testing.assert_allclose(np.asarray(dev), host.astype(np.float32),
                               rtol=1e-5, atol=1e-6)
