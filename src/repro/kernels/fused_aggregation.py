"""Fused dequantize → staleness-decay → masked Eq. 1 reduction (Pallas).

The fog node's per-round tail is the aggregation over the stacked ``[D,
...]`` device axis: reconstruct each upload (int8 dequantize or top-k
scatter), weight it by ``raw_i · decay(staleness_i) · mask_i`` normalized
over arrivals (``aggregation.masked_normalize``), and reduce Eq. 1 —
today three separate XLA ops that each stream the full ``[D, N]`` payload
through HBM.  At D ≥ 1k that traffic IS the round tail (Kumar & Srirama;
FORA).  This kernel does the whole chain in ONE pass over the device
axis: every feature tile is read once, dequantized in-register, weighted,
and segment-reduced on the MXU.

Layout (DESIGN.md §5 / the acquisition-scores kernel's TPU adaptation):
the pytree is flattened to one ``[D, N]`` matrix, D padded to the 128
lane width (the per-device meta vectors ride with D on the LANE axis),
N padded to ``block_n`` tiles.  Per grid step the kernel holds one
``[Dp, bn]`` payload tile plus the tiny ``[8, Dp]`` meta block (raw
weights, staleness, mask, segment id) and the ``[Dp, Lp]`` per-tensor
scale table in VMEM.  Segment membership is a one-hot ``[Gp, Dp]``
matrix built from an iota compare, so the masked-normalize segment sums
AND the final reduction are all MXU matmuls — no gathers, no scatters.
Padded device rows carry zero weight/mask and a DUMMY segment id (G), so
the ``masked_normalize`` size/uniform fallbacks see exactly the real
D rows; the dummy output row is sliced off.

Numerics: the weight chain (decay → per-segment normalize with the
zero-sum→uniform guards) matches ``aggregation.masked_normalize``
formula-for-formula in f32; the reduction accumulates f32 regardless of
payload dtype (f32 / bf16 / int8) and casts to the leaf dtype (f32 for
quantized inputs) on the way out — the same contract as
``aggregation.weighted_sum_stacked`` / ``topology.segment_sum_stacked``.
Summation ORDER differs from the jnp oracle (MXU dot vs axis-0 sum), so
parity with ``kernels.ref.fused_agg_ref`` is to float tolerance (≤1e-5
fp32), pinned by tests/test_fused_aggregation.py.

On CPU (CI) the kernel runs in Pallas interpret mode — functional, not
fast.  ``tests/test_tpu_compile.py`` compiles it for a described v5e and
``chip_smoke.py`` checks it against the reference on a TPU.
``interpret=None`` auto-selects.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DECAY_KINDS = ("none", "exp", "poly")


def _ceil_to(n: int, m: int) -> int:
    return max(m, -(-n // m) * m)


def _dot(a, b):
    # full f32 contraction on the MXU: Mosaic's default f32 matmul may take
    # reduced-precision passes, which would round weights and scales
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _kernel(x_ref, meta_ref, scales_ref, lid_ref, out_ref, *,
            kind: str, rate: float, normalize: bool, quantized: bool):
    meta = meta_ref[...]                                  # [8, Dp] f32
    raw, stale, mask, segf = (meta[0:1], meta[1:2], meta[2:3], meta[3:4])
    # decay(s): decay(0) == 1 exactly for every kind (aggregation
    # .staleness_decay contract — the zero-straggler round stays sync)
    if kind == "exp":
        dec = jnp.power(jnp.float32(rate), stale)
    elif kind == "poly":
        dec = jnp.power(1.0 + stale, -jnp.float32(rate))
    else:
        dec = jnp.ones_like(stale)
    w = raw * dec * mask                                  # [1, Dp]

    Gp, Dp = out_ref.shape[0], w.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (Gp, Dp), 0)
    onehot = (rows == segf.astype(jnp.int32)).astype(jnp.float32)  # [Gp, Dp]

    if normalize:
        # masked_normalize, segment form, formula-for-formula: per-segment
        # Σw / Σm / size via one-hot matmuls, gathered back per row by the
        # transpose matmul (flat mode is the 1-segment special case)
        def seg_tot(v):                                   # [1, Dp] → [1, Dp]
            tot = _dot(onehot, v.T)                       # [Gp, 1]
            return _dot(tot.T, onehot)                    # [1, Dp]

        wsum = seg_tot(w)
        msum = seg_tot(mask)
        size = seg_tot(jnp.ones_like(mask))
        uniform = jnp.where(msum > 0, mask / jnp.maximum(msum, 1.0),
                            1.0 / jnp.maximum(size, 1.0))
        alpha = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), uniform)
    else:
        alpha = w

    val = x_ref[...].astype(jnp.float32)                  # [Dp, bn]
    if quantized:
        # per-(device, tensor) scale select as a one-hot matmul over the
        # leaf-id row — dequantize stays on the MXU, no per-column gather
        lid = lid_ref[0:1, :]                             # [1, bn] f32 ids
        Lp = scales_ref.shape[1]
        lrows = jax.lax.broadcasted_iota(jnp.int32, (Lp, lid.shape[1]), 0)
        sel = (lrows == lid.astype(jnp.int32)).astype(jnp.float32)  # [Lp, bn]
        scale = _dot(scales_ref[...], sel)                # [Dp, bn]
        val = val * scale

    out_ref[...] = _dot(onehot * alpha, val)              # [Gp, bn]


def fused_aggregate(stacked, weights, *, staleness=None, mask=None,
                    kind: str = "none", rate: float = 0.5,
                    normalize: bool = True, segment_ids=None,
                    num_segments: Optional[int] = None, scales=None,
                    out_dtype=None, block_n: int = 512,
                    interpret: Optional[bool] = None):
    """One-pass fused fog aggregation over the stacked device axis.

    ``stacked`` is a ``[D, ...]`` pytree of payloads (f32 / bf16 deltas,
    or int8 codes when ``scales`` — a matching pytree of per-device
    per-tensor f32 scales ``[D]`` — is given, in which case dequantize
    fuses into the same pass).  ``weights`` ``[D]`` is the raw Eq. 1
    basis; with ``normalize=True`` the kernel applies
    ``staleness_decay(kind, rate)`` and the full ``masked_normalize``
    arrival guard chain in-kernel; with ``normalize=False`` the weights
    are applied AS-IS — the engines' mode, since under ``shard_map``
    each shard must reduce its local rows with GLOBALLY normalized
    coefficients and psum the partials (renormalizing locally would be
    wrong), exactly like ``weighted_sum_stacked``.

    Flat mode returns the ``[...]`` reduced pytree; with ``segment_ids``
    ``[D]`` + static ``num_segments`` it returns ``[G, ...]`` per-group
    partials (``topology.segment_sum_stacked``'s contract).  Output
    leaves cast to ``out_dtype`` (default: the input leaf dtype, or f32
    for quantized payloads — both matching the jnp reference).

    ``interpret=None`` auto-selects Pallas interpret mode off-TPU (CPU
    CI runners); parity with ``kernels.ref.fused_agg_ref`` is pinned by
    tests/test_fused_aggregation.py.
    """
    if kind not in DECAY_KINDS:
        raise ValueError(
            f"unknown staleness decay {kind!r}: use {' | '.join(DECAY_KINDS)}")
    if segment_ids is not None and num_segments is None:
        raise ValueError("segment_ids requires a static num_segments")
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    if not leaves:
        return stacked
    quantized = scales is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    D = leaves[0].shape[0]
    G = 1 if segment_ids is None else int(num_segments)

    flat = [l.reshape(D, -1) for l in leaves]
    sizes = [f.shape[1] for f in flat]
    x = jnp.concatenate(flat, axis=1) if len(flat) > 1 else flat[0]
    N = x.shape[1]
    bn = int(block_n)
    N_pad = _ceil_to(N, bn)
    # D rides the LANE axis of the meta/one-hot blocks → 128 multiple;
    # that also over-satisfies every payload-dtype sublane granule
    Dp = _ceil_to(D, 128)
    Gp = _ceil_to(G + 1, 8)                   # +1: dummy segment for pads
    x = jnp.pad(x, ((0, Dp - D), (0, N_pad - N)))

    def _vec(v, fill):
        row = (jnp.full((D,), fill, jnp.float32) if v is None
               else jnp.asarray(v, jnp.float32))
        return jnp.pad(row, (0, Dp - D))      # pads: weight 0, mask 0

    segf = (jnp.zeros((D,), jnp.float32) if segment_ids is None
            else jnp.asarray(segment_ids, jnp.int32).astype(jnp.float32))
    segf = jnp.pad(segf, (0, Dp - D), constant_values=float(G))
    zero = jnp.zeros((Dp,), jnp.float32)
    meta = jnp.stack([_vec(weights, 1.0), _vec(staleness, 0.0),
                      _vec(mask, 1.0), segf, zero, zero, zero, zero])

    if quantized:
        s_leaves = jax.tree_util.tree_leaves(scales)
        if len(s_leaves) != len(leaves):
            raise ValueError(
                f"scales tree has {len(s_leaves)} leaves for "
                f"{len(leaves)} payload leaves")
        smat = jnp.stack([jnp.asarray(s, jnp.float32).reshape(D)
                          for s in s_leaves], axis=1)             # [D, L]
        lid = jnp.concatenate(
            [jnp.full((n,), i, jnp.float32) for i, n in enumerate(sizes)])
    else:
        smat = jnp.ones((D, 1), jnp.float32)
        lid = jnp.zeros((N,), jnp.float32)
    Lp = _ceil_to(smat.shape[1], 128)
    smat = jnp.pad(smat, ((0, Dp - D), (0, Lp - smat.shape[1])))
    lid = jnp.broadcast_to(jnp.pad(lid, (0, N_pad - N))[None, :],
                           (8, N_pad))

    out = pl.pallas_call(
        functools.partial(_kernel, kind=kind, rate=float(rate),
                          normalize=bool(normalize),
                          quantized=quantized),
        grid=(N_pad // bn,),
        in_specs=[
            pl.BlockSpec((Dp, bn), lambda i: (0, i)),
            pl.BlockSpec((8, Dp), lambda i: (0, 0)),
            pl.BlockSpec((Dp, Lp), lambda i: (0, 0)),
            pl.BlockSpec((8, bn), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((Gp, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((Gp, N_pad), jnp.float32),
        interpret=interpret,
        name="fused_aggregation",
    )(x, meta, smat, lid)

    res = out[:G, :N]
    outs, off = [], 0
    for leaf, n in zip(leaves, sizes):
        dt = out_dtype if out_dtype is not None else (
            jnp.float32 if quantized else leaf.dtype)
        block = res[:, off:off + n]
        shape = leaf.shape[1:]
        outs.append((block[0].reshape(shape) if segment_ids is None
                     else block.reshape((G,) + shape)).astype(dt))
        off += n
    return jax.tree_util.tree_unflatten(treedef, outs)
