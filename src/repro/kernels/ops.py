"""Public kernel ops: jit'd wrappers that dispatch Pallas on TPU and the
pure-jnp oracle (ref.py) elsewhere — the dry-run path lowers the oracle
because Pallas-TPU cannot compile on a CPU backend (DESIGN.md §2).

``implementation`` ∈ {"auto", "pallas", "pallas_interpret", "xla"}
("ref" is accepted as an alias for "xla" — the pure-jnp reference twins in
``ref.py`` ARE the XLA path).

The ``REPRO_KERNELS_IMPL`` environment variable overrides what ``"auto"``
resolves to (explicit ``implementation=`` arguments always win).  CI's
``pallas-interpret`` job sets it to ``pallas_interpret`` so the Pallas
kernel bodies — not just the XLA fallbacks — are exercised on CPU runners.
Interpret mode is never a silent fallback: ``"pallas"`` on a backend other
than the TPU raises.

LM-side kernels (flash_attention / stc_compress / ssm_scan / ssd_scan) are
joined by the FL diffusion data plane (mix_aggregate / stc_topk /
dol_bid_scores — ``kernels/diffusion.py``), which the executors, fedshard
and the planner call through the same dispatch so one env var flips the
whole system between Pallas and reference bodies.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.diffusion import (bid_value_fuse_pallas,
                                     dol_bid_scores_pallas,
                                     mix_aggregate_pallas, stack_ravel,
                                     stack_unravel, stc_rows_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quant import quant_pack_pallas, quant_unpack_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas
from repro.kernels.stc_compress import stc_apply_pallas, stc_reduce_pallas

__all__ = ["flash_attention", "stc_compress", "ssm_scan", "ssd_scan",
           "mix_aggregate", "mix_aggregate_tree", "stc_topk",
           "dol_bid_scores", "bid_value_fuse", "quant_pack", "quant_unpack"]

_IMPLS = ("pallas", "pallas_interpret", "xla", "ref")


def _resolve(implementation: str) -> str:
    if implementation != "auto":
        return "xla" if implementation == "ref" else implementation
    forced = os.environ.get("REPRO_KERNELS_IMPL", "")
    if forced:
        if forced not in _IMPLS:
            raise ValueError(
                f"REPRO_KERNELS_IMPL={forced!r}: expected one of {_IMPLS}")
        return "xla" if forced == "ref" else forced
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _interpret(impl: str) -> bool:
    """Whether a resolved Pallas implementation runs interpreted.

    Interpret mode happens only when asked for ("pallas_interpret", directly
    or through ``REPRO_KERNELS_IMPL``).  An explicit "pallas" off a TPU
    raises instead of interpreting, so no run can look as though it took
    the compiled kernel path without touching the chip."""
    if impl == "pallas_interpret":
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"implementation='pallas' compiles for a TPU, but the backend is "
            f"{backend!r}; ask for 'pallas_interpret' to run the kernel "
            f"bodies in interpret mode")
    return False


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    implementation: str = "auto") -> jax.Array:
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale,
                                  interpret=_interpret(impl))


def stc_compress(x, sparsity: float = 0.01, *,
                 implementation: str = "auto") -> jax.Array:
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.stc_compress_ref(x, sparsity)
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    k = max(1, int(n * sparsity))
    # τ = k-th largest |x| (global sort: stays in XLA; see stc_compress.py)
    thr = jnp.sort(jnp.abs(flat))[n - k]
    interpret = _interpret(impl)
    ssum, cnt = stc_reduce_pallas(flat, thr, interpret=interpret)
    mu = ssum / jnp.maximum(cnt, 1.0)
    out = stc_apply_pallas(flat, thr, mu, interpret=interpret)
    return out.reshape(x.shape).astype(x.dtype)


def mix_aggregate(x, w, *, implementation: str = "auto") -> jax.Array:
    """Eq. (10)/(11) fused mix/aggregate: x (C, F) client-stacked flat
    params, w (G, C) weights (a MixOp matrix, an aggregation row, or a
    sharded Wᵀ block) → (G, F) fp32 in one pass."""
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.mix_aggregate_ref(x, w)
    interpret = _interpret(impl)
    return mix_aggregate_pallas(x, w, interpret=interpret)


def mix_aggregate_tree(params, w, *, collapse: bool = False,
                       keep_float32: bool = False,
                       implementation: str = "auto"):
    """Tree-level Eq. (10)/(11): mix/aggregate a client-stacked pytree.

    ``w`` is (G, C): a (C, C) MixOp matrix, a (1, C) Eq.-11 aggregation
    row, or a Wᵀ shard block.  ``collapse=True`` (aggregation) drops the
    leading slot axis — explicit rather than inferred from G=1, so a
    one-slot MixOp stays stacked.  ``keep_float32=True`` returns fp32
    leaves (for sharded partials that still cross a psum); otherwise leaf
    dtypes are preserved.

    Dispatch picks the *placement*, not just the body: the XLA path runs
    the per-leaf einsum chain (XLA-CPU fuses it well, and concatenating
    leaves costs a real copy there), while the Pallas path flattens the
    fleet once and streams it through :func:`mix_aggregate` in a single
    HBM pass — the per-leaf chain re-reads HBM per leaf on TPU.
    """
    impl = _resolve(implementation)
    w = jnp.asarray(w, jnp.float32)
    if collapse:
        assert w.shape[0] == 1, w.shape
    if impl == "xla":
        def leaf(x):
            out = jnp.einsum("gc,c...->g...", w, x.astype(jnp.float32))
            if not keep_float32:
                out = out.astype(x.dtype)
            return out[0] if collapse else out
        return jax.tree.map(leaf, params)
    flat, spec = stack_ravel(params)
    out = mix_aggregate(flat, w, implementation=impl)
    return stack_unravel(out, spec, collapse=collapse,
                         keep_float32=keep_float32)


def stc_topk(x, ref_row, mask, sparsity: float = 0.01, *,
             implementation: str = "auto") -> jax.Array:
    """Masked per-row (per-client) STC against a shared reference row —
    the D2D hop compression of ``fedshard.masked_stc_compress`` on one
    flattened leaf.  x (C, n); ref_row (n,); mask (C,) bool."""
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.stc_rows_ref(x, ref_row, mask, sparsity)
    interpret = _interpret(impl)
    return stc_rows_pallas(x, ref_row, mask, sparsity, interpret=interpret)


def quant_pack(x, *, implementation: str = "auto"):
    """Per-row int8 absmax pack — the adapter hop wire format.  x (R, B)
    fp32 → (q (R, B) int8, scale (R,) fp32), ``scale = max(absmax,
    1e-12)/127`` per row.  Rows here are the QUANT_BLOCK-element row-blocks
    of a flattened adapter (``fl/adapters.pack_rows``)."""
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.quant_pack_ref(x)
    interpret = _interpret(impl)
    return quant_pack_pallas(x, interpret=interpret)


def quant_unpack(q, scale, *, implementation: str = "auto") -> jax.Array:
    """Inverse of :func:`quant_pack`: (q (R, B) int8, scale (R,)) → (R, B)
    fp32 dequantized payload at the hop destination."""
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.quant_unpack_ref(q, scale)
    interpret = _interpret(impl)
    return quant_unpack_pallas(q, scale, interpret=interpret)


def dol_bid_scores(dol, chain_size, dsi, data_size, *,
                   metric: str = "w1_norm",
                   implementation: str = "auto") -> jax.Array:
    """The planner's (M, N) candidate IID-distance matrix (Eq. 32 bids).

    The Pallas path implements the paper's default ``w1_norm`` metric
    (Eq. B.1) as a tiled MXU contraction; the Appendix-C divergence
    metrics (kld/jsd/w1_true) have no closed matmul form and always use
    the reference composite.
    """
    impl = _resolve(implementation)
    if impl == "xla" or metric != "w1_norm":
        return ref.dol_bid_scores_ref(dol, chain_size, dsi, data_size,
                                      metric)
    interpret = _interpret(impl)
    return dol_bid_scores_pallas(dol, chain_size, dsi, data_size,
                                 interpret=interpret)


def bid_value_fuse(bids, value, weight, *,
                   implementation: str = "auto") -> jax.Array:
    """Fuse the per-client learning value into the planner's bid matrix:
    ``bids · (1 + weight · value[None, :])`` — the uncertainty-weighted
    auction objective next to :func:`dol_bid_scores`."""
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.bid_value_fuse_ref(bids, value, weight)
    interpret = _interpret(impl)
    return bid_value_fuse_pallas(bids, value, weight, interpret=interpret)


def ssm_scan(da, dbx, *, implementation: str = "auto") -> jax.Array:
    impl = _resolve(implementation)
    if impl == "xla":
        return ref.ssm_scan_ref(da, dbx)
    interpret = _interpret(impl)
    return ssm_scan_pallas(da, dbx, interpret=interpret)


def ssd_scan(xh, a, bmat, cmat, *, implementation: str = "auto") -> jax.Array:
    """Mamba-2 SSD chunk scan (zamba2)."""
    from repro.kernels.ssd_scan import ssd_scan_pallas, ssd_scan_ref
    impl = _resolve(implementation)
    if impl == "xla":
        return ssd_scan_ref(xh, a, bmat, cmat)
    interpret = _interpret(impl)
    return ssd_scan_pallas(xh, a, bmat, cmat, interpret=interpret)
