"""Fused MC-dropout acquisition-score kernel (Pallas TPU).

The paper's edge-side hot loop is: T stochastic forwards over a pool window,
then per-point uncertainty statistics (Eqs. 2–4). Computed naively, the
[T, N, C] log-prob tensor is read from HBM once per statistic (entropy,
BALD, VR) — 3× the traffic of one pass. This kernel fuses all three into a
single VMEM-resident pass over pool tiles: for each [T, C, bn] tile it
computes the MC-mean posterior once and emits entropy / BALD / VR together.

TPU adaptation: the pool axis rides the 128-lane width and the class axis
the sublanes (C padded to a multiple of 8), so every per-point statistic
is a sublane reduction that lands lane-dense in a ``[1, bn]`` output row —
the layout Mosaic needs for any pool size.  The T reduction happens in
VREGs.

Grid: (N_pad // bn,). BlockSpecs keep [T, C_pad, bn] in VMEM
(T=16, C=10 → C_pad=16, bn=128 → 128 KB fp32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-10
_NEG = -1e30


def _kernel(logp_ref, ent_ref, bald_ref, vr_ref, *, n_classes: int):
    logp = logp_ref[...]                                 # [T, C_pad, bn] f32
    # mask padded classes: contribute 0 probability
    class_ok = jax.lax.broadcasted_iota(jnp.int32, logp.shape, 1) < n_classes
    logp = jnp.where(class_ok, logp, _NEG)

    p = jnp.exp(logp)                                    # [T, C, bn]
    pbar = jnp.mean(p, axis=0)                           # [C, bn]
    log_pbar = jnp.log(pbar + _EPS)

    ent = -jnp.sum(jnp.where(class_ok[0], pbar * log_pbar, 0.0), axis=0,
                   keepdims=True)                                        # [1, bn]
    exp_ent = -jnp.mean(
        jnp.sum(jnp.where(class_ok, p * logp, 0.0), axis=1, keepdims=True),
        axis=0)                                                          # [1, bn]
    vr = 1.0 - jnp.max(pbar, axis=0, keepdims=True)                      # [1, bn]

    ent_ref[...] = ent
    bald_ref[...] = ent - exp_ent
    vr_ref[...] = vr


def acquisition_scores_fused(log_probs, *, block_n: int = 128,
                             interpret: bool = False):
    """log_probs: [T, N, C] → (entropy [N], bald [N], vr [N]) in one pass."""
    T, N, C = log_probs.shape
    C_pad = -(-C // 8) * 8
    N_pad = -(-N // block_n) * block_n
    x = jnp.pad(jnp.swapaxes(log_probs.astype(jnp.float32), 1, 2),
                ((0, 0), (0, C_pad - C), (0, N_pad - N)),
                constant_values=_NEG)                    # [T, C_pad, N_pad]

    ent, bald, vr = pl.pallas_call(
        functools.partial(_kernel, n_classes=C),
        grid=(N_pad // block_n,),
        in_specs=[pl.BlockSpec((T, C_pad, block_n), lambda i: (0, 0, i))],
        out_specs=[pl.BlockSpec((1, block_n), lambda i: (0, i))] * 3,
        out_shape=[jax.ShapeDtypeStruct((1, N_pad), jnp.float32)] * 3,
        interpret=interpret,
        name="acquisition_scores",
    )(x)
    return ent[0, :N], bald[0, :N], vr[0, :N]
