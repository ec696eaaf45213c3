"""Int8 absmax quantization kernels (kernels/quant.py) and the adapter-hop
packing layer over them (repro.fl.adapters): Pallas-vs-ref parity, dispatch
plumbing, the wire-format invariants (idempotence, zero rows, error bound)
and the packed-bits payload accounting the Eq.-15 ledger charges."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fl.adapters import (QUANT_BLOCK, pack_rows, packed_bits,
                               quant_roundtrip_rows, quant_roundtrip_slot,
                               quant_roundtrip_tree, unpack_rows)
from repro.kernels import ops
from repro.kernels.quant import quant_pack_pallas, quant_unpack_pallas
from repro.kernels.ref import quant_pack_ref, quant_unpack_ref

RNG = np.random.default_rng(11)


def _rows(r, b, zero_row=True):
    x = RNG.normal(size=(r, b)).astype(np.float32) * RNG.uniform(
        0.01, 100.0, size=(r, 1)).astype(np.float32)
    if zero_row:
        x[0] = 0.0
    return jnp.asarray(x)


# --------------------------------------------------------- kernel parity

@pytest.mark.parametrize("r,b", [(1, 512), (7, 512), (16, 128), (3, 8),
                                 (57, 512),    # one tile of all 57 rows
                                 (300, 512)])  # ragged second 256-row tile
def test_pack_pallas_matches_ref_bitwise(r, b):
    """Same int8 codes and bit-identical fp32 scales on both bodies — the
    wire format cannot depend on which implementation packed it."""
    x = _rows(r, b)
    q_p, s_p = quant_pack_pallas(x, interpret=True)
    q_r, s_r = quant_pack_ref(x)
    np.testing.assert_array_equal(np.asarray(q_p), np.asarray(q_r))
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
    out_p = quant_unpack_pallas(q_p, s_p, interpret=True)
    out_r = quant_unpack_ref(q_r, s_r)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_r))


def test_zero_rows_quantize_to_exact_zero():
    """All-zero rows hit the ε absmax floor and decode to exact zeros —
    padded mesh slots must stay inert through a packed hop."""
    x = jnp.zeros((4, QUANT_BLOCK), jnp.float32)
    q, s = quant_pack_pallas(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(q), 0)
    out = quant_unpack_pallas(q, s, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_roundtrip_error_bounded_by_half_scale():
    x = _rows(9, QUANT_BLOCK, zero_row=False)
    q, s = quant_pack_pallas(x, interpret=True)
    out = quant_unpack_pallas(q, s, interpret=True)
    err = np.abs(np.asarray(out) - np.asarray(x))
    bound = np.asarray(s)[:, None] * (0.5 + 1e-3)
    assert (err <= bound).all()


def test_roundtrip_is_stable():
    """Re-packing a decoded payload (a multi-round diffusion chain: one
    roundtrip per hop) keeps the int8 codes bit-identical; only the scale
    can move by 1 ulp (absmax lands exactly on 127·scale, and
    (127·s)·(1/127) re-rounds), so values stay within 1 ulp relative."""
    x = _rows(5, QUANT_BLOCK)
    once = quant_roundtrip_rows(x)
    twice = quant_roundtrip_rows(once)
    q1, _ = pack_rows(once)
    q2, _ = pack_rows(twice)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(once), np.asarray(twice),
                               rtol=1.5e-7, atol=0.0)


def test_pack_vmaps():
    """The fleet/sharded planes pack under vmap (client-stacked batch)."""
    xs = jnp.stack([_rows(4, 128, zero_row=False) for _ in range(3)])
    q_v, s_v = jax.vmap(lambda a: quant_pack_pallas(a, interpret=True))(xs)
    for i in range(3):
        q, s = quant_pack_pallas(xs[i], interpret=True)
        np.testing.assert_array_equal(np.asarray(q_v[i]), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(s_v[i]), np.asarray(s))


# -------------------------------------------------------------- dispatch

def test_ops_dispatch_honors_implementation_and_env(monkeypatch):
    x = _rows(4, 64)
    want_q, want_s = quant_pack_ref(x)
    for impl in ("ref", "xla", "pallas_interpret"):
        q, s = ops.quant_pack(x, implementation=impl)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(want_q))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(want_s))
        out = ops.quant_unpack(q, s, implementation=impl)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(quant_unpack_ref(want_q, want_s)))
    monkeypatch.setenv("REPRO_KERNELS_IMPL", "pallas_interpret")
    q, s = ops.quant_pack(x, implementation="auto")
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want_q))
    monkeypatch.setenv("REPRO_KERNELS_IMPL", "ref")
    q, s = ops.quant_pack(x, implementation="auto")
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want_q))


# ------------------------------------------------- adapter packing layer

def test_pack_rows_pads_to_block_multiple():
    """F not a block multiple: the pad decodes away and padded tail codes
    are zeros (they ride the wire but never perturb the payload)."""
    c, f = 3, QUANT_BLOCK + 37
    flat = _rows(c, f, zero_row=False)
    q, s = pack_rows(flat)
    assert q.shape == (c, 2 * QUANT_BLOCK) and s.shape == (c, 2)
    out = unpack_rows(q, s, f)
    assert out.shape == (c, f)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(quant_roundtrip_rows(flat)))


def test_packed_bits_formula_and_shape_structs():
    """8·block + 32 bits per row-block, computed from shapes alone — the
    same figure whether the template holds arrays or eval_shape structs."""
    tmpl = {"a": jnp.zeros((3, 100)), "b": jnp.zeros((41,))}
    f = 341
    rows = -(-f // QUANT_BLOCK)
    assert packed_bits(tmpl) == float(rows * (8 * QUANT_BLOCK + 32))
    structs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tmpl)
    assert packed_bits(structs) == packed_bits(tmpl)
    assert packed_bits(tmpl) < 32.0 * f * 2   # beats fp32 well before 4x


def test_slot_and_tree_roundtrips_share_block_layout():
    """HostExecutor decodes slot trees, the stacked planes decode (C, F)
    blocks; identical row-block boundaries mean identical decoded values —
    the cross-executor parity invariant."""
    def tree(k):
        g = np.random.default_rng(k)
        return {"a": jnp.asarray(g.normal(size=(13, 5)), jnp.float32),
                "b": [jnp.asarray(g.normal(size=(700,)), jnp.float32),
                      jnp.asarray(g.normal(size=(2, 3)), jnp.float32)]}
    slots = [tree(i) for i in range(4)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *slots)
    via_tree = quant_roundtrip_tree(stacked)
    for i, slot in enumerate(slots):
        via_slot = quant_roundtrip_slot(slot)
        for a, b in zip(jax.tree.leaves(via_slot),
                        jax.tree.leaves(jax.tree.map(lambda x: x[i],
                                                     via_tree))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
