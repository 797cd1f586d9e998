"""Fog-node aggregation strategies (paper §III-B, Eq. 1).

Two families:

* **List variants** (``fedavg`` / ``weighted_average`` / ``opt_model``) take a
  Python list of per-device parameter pytrees — the legacy fog-node path, one
  pytree per upload.
* **Stacked variants** (``fedavg_stacked`` / ``weighted_average_stacked`` /
  ``opt_model_stacked``) operate directly on the engine's ``[D, ...]`` stacked
  state, so Eq. 1 is a handful of fused reductions instead of a D-long
  Python fold — and, crucially, they are pure traced functions that the
  vectorized engine can compile *into* the round program
  (``EdgeEngine.run_rounds_fused``), eliminating the O(D) host-side
  aggregation tail entirely.

``exclude`` is a predicate on the flattened key path used to keep per-device
state (e.g. recurrent states, batch statistics) out of the average —
relevant for the hybrid/SSM architectures (DESIGN.md §4).

Weight hygiene (paper Eq. 1 writes W ← Σ_i α_i W_i with Σα = 1):
``normalize_weights`` restricts the raw weights to the participation mask
and guards the Σw = 0 corner (all device val-accs zero in an early round
used to propagate NaN into every parameter) by falling back to a uniform
average over participants.

The Σα = 1 guarantee is LOAD-BEARING beyond hygiene: it makes Eq. 1 exact
in DELTA form, W ← W_prev + Σ_i α_i (W_i − W_prev), which is how the fused
engine aggregates compressed uploads (``core.comms``: each device ships a
quantized/sparsified Δ_i, never full weights).  Any change that lets
normalized weights sum to ≠ 1 silently corrupts every compressed round.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def masked_normalize(weights, mask=None, *, segment_ids=None,
                     num_segments: Optional[int] = None) -> jax.Array:
    """THE arrival-weight normalization: raw weights → convex coefficients.

    Every Eq. 1 weighting in the repo funnels through here —
    ``normalize_weights`` (and with it ``fedavg_n`` /
    ``weighted_average_stacked``), ``staleness_weights``, and the engine's
    guard/topology re-normalizations — so the zero-sum→uniform NaN guard
    lives in exactly one place:

    * Σ(w·mask) = 0 over a (segment's) participants → uniform over those
      participants;
    * no participants at all → uniform over the whole (segment's) slot set.

    Flat mode (``segment_ids=None``): one normalization over the full
    vector, Σα = 1.  Segment mode (``segment_ids`` [D] int, ``num_segments``
    G static): an independent normalization per segment — the intra-fog
    Eq. 1 coefficients of ``core.topology``, with the same per-segment
    degenerate-case guards, Σ_{i∈g} α_i = 1 for every segment g.  Fully
    traced — safe under jit/vmap/shard_map.
    """
    w = jnp.asarray(weights, jnp.float32)
    m = jnp.ones_like(w) if mask is None else jnp.asarray(mask, jnp.float32)
    w = w * m
    if segment_ids is None:
        wsum = jnp.sum(w)
        msum = jnp.sum(m)
        uniform = jnp.where(msum > 0, m / jnp.maximum(msum, 1.0),
                            jnp.full_like(w, 1.0 / w.shape[0]))
        return jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), uniform)
    if num_segments is None:
        raise ValueError("segment_ids requires a static num_segments")
    ids = jnp.asarray(segment_ids, jnp.int32)
    wsum = jax.ops.segment_sum(w, ids, num_segments=num_segments)[ids]
    msum = jax.ops.segment_sum(m, ids, num_segments=num_segments)[ids]
    size = jax.ops.segment_sum(jnp.ones_like(w), ids,
                               num_segments=num_segments)[ids]
    uniform = jnp.where(msum > 0, m / jnp.maximum(msum, 1.0),
                        1.0 / jnp.maximum(size, 1.0))
    return jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30), uniform)


def normalize_weights(weights, mask=None) -> jax.Array:
    """Raw per-device weights → convex combination coefficients α (Eq. 1).

    ``mask`` (optional, [D] bool/float) zeroes out non-participants (the
    paper's asynchronization tolerance: devices that did not upload this
    round).  Degenerate cases fall back instead of producing NaN — see
    ``masked_normalize``, the single home of that guard."""
    return masked_normalize(weights, mask)


def staleness_decay(staleness, *, kind: str = "exp",
                    rate: float = 0.5) -> jax.Array:
    """Per-device staleness discount ``decay(s_i)`` for Eq. 1 weighting.

    ``staleness`` is the [D] age (in rounds) of each device's buffered
    update (0 = fresh, this round's work).  ``exp``: ``rate**s`` (rate ∈
    (0, 1], the per-round factor); ``poly``: ``(1 + s)**-rate`` (Xie et
    al.'s polynomial staleness weighting from async FL); ``none``: 1 —
    staleness ignored, weights reduce to their synchronous form.  Fully
    traced; decay(0) == 1 exactly for every kind, which is what makes the
    zero-straggler hetero round numerically the synchronous round.
    """
    s = jnp.asarray(staleness, jnp.float32)
    if kind == "none":
        return jnp.ones_like(s)
    if kind == "exp":
        return jnp.power(jnp.float32(rate), s)
    if kind == "poly":
        return jnp.power(1.0 + s, -jnp.float32(rate))
    raise ValueError(f"unknown staleness decay {kind!r}: use none | exp | poly")


def staleness_weights(raw, staleness, mask=None, *, kind: str = "exp",
                      rate: float = 0.5, segment_ids=None,
                      num_segments: Optional[int] = None) -> jax.Array:
    """Staleness-aware Eq. 1 coefficients: ``alpha_i ∝ raw_i · decay(s_i)``
    normalized over the ``mask`` arrivals (zero-sum guarded in
    ``masked_normalize``, the single home of that guard).  ``raw`` is the
    synchronous weight basis — labeled counts n_i for ``fedavg_n``,
    validation accuracy, or ones — so ``kind="none"`` (or all-zero
    staleness) reduces exactly to the synchronous weighting over arrivals.
    With ``segment_ids``/``num_segments`` the normalization is per fog
    group (intra-fog Eq. 1 — see ``core.topology``)."""
    w = jnp.asarray(raw, jnp.float32) * staleness_decay(
        staleness, kind=kind, rate=rate)
    return masked_normalize(w, mask, segment_ids=segment_ids,
                            num_segments=num_segments)


def weighted_average(models: Sequence, weights: Sequence[float], *,
                     exclude: Optional[Callable[[str], bool]] = None):
    """W ← Σ_i α_i W_i (paper Eq. 1) over a list of pytrees.

    ``weights`` are normalized here (zero-sum guarded — see
    ``normalize_weights``).  Excluded leaves take the first model's value
    (the fog node's own copy).
    """
    w = normalize_weights(jnp.asarray(weights, jnp.float32))

    def agg(path, *leaves):
        if exclude is not None and exclude(_path_str(path)):
            return leaves[0]
        acc = sum(wi * l.astype(jnp.float32) for wi, l in zip(w, leaves))
        return acc.astype(leaves[0].dtype)

    return jax.tree_util.tree_map_with_path(agg, models[0], *models[1:])


def fedavg(models: Sequence, *, exclude: Optional[Callable[[str], bool]] = None):
    """Uniform-α federated averaging — the paper's default."""
    return weighted_average(models, [1.0] * len(models), exclude=exclude)


def fedavg_n(models: Sequence, counts: Sequence[float], *,
             exclude: Optional[Callable[[str], bool]] = None):
    """Size-aware Eq. 1: α_i ∝ n_i, the device's labeled-sample count.

    The correct weighting for the unbalanced shards ``federated_split``
    produces (cf. hierarchical fog aggregation in Kumar & Srirama 2024,
    Hussain 2022); uniform ``fedavg`` over-weights small shards.
    """
    return weighted_average(models, counts, exclude=exclude)


def opt_model(models: Sequence, scores: Sequence[float]):
    """Paper's 'choosing the best-trained model': argmax validation score."""
    best = int(jnp.argmax(jnp.asarray(scores)))
    return models[best], best


# --------------------------------------------------------------- stacked axis
def weighted_sum_stacked(stacked, w, *, out_dtype=None,
                         exclude: Optional[Callable[[str], bool]] = None):
    """Σ_i w_i · leaf[i] over the leading device axis; ``w`` [D] is applied
    as-is (already normalized — see ``normalize_weights``).  Accumulates in
    f32 and casts each output leaf to ``out_dtype`` (default: the leaf's own
    dtype — the storage-dtype discipline a bf16 fleet over an fp32 master
    relies on).  Excluded leaves take device 0's slice.  The building block
    the engine psum-reduces under ``shard_map`` (each shard contributes its
    local partial sum).

    CAVEAT: ``exclude`` composes with the single-host stacked path only —
    inside a shard_map'd program a psum over the result would SUM each
    shard's local device-0 slice of an excluded leaf instead of selecting
    global device 0's.  The engines therefore never pass ``exclude`` here:
    they thread the adapter's ``aggregate_mask`` themselves (zero excluded
    leaves out of the upload deltas, keep each device's own copy at
    re-dispatch, and report GLOBAL slot 0 via a one-hot representative row
    + fleet psum — see ``engine._get_rounds_fused_jit`` / the async
    mirror), which is mesh-exact."""

    def agg(path, leaf):
        if exclude is not None and exclude(_path_str(path)):
            return leaf[0]
        wb = w.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(wb * leaf.astype(jnp.float32), axis=0).astype(
            leaf.dtype if out_dtype is None else out_dtype)

    return jax.tree_util.tree_map_with_path(agg, stacked)


# ----------------------------------------------------- Eq. 1 reduce routing
AGG_IMPLS = ("auto", "ref", "pallas", "pallas_interpret")


def resolve_aggregate_impl(impl: Optional[str]) -> str:
    """``auto`` → the fused Pallas kernel on TPU, the jnp reference
    elsewhere (interpret-mode Pallas is functional but slow on CPU — the
    same policy as ``engine.resolve_scorer``).  An explicit ``"pallas"``
    off-TPU raises rather than quietly interpreting."""
    if impl in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in AGG_IMPLS:
        raise ValueError(
            f"unknown aggregate_impl {impl!r}: use {' | '.join(AGG_IMPLS)}")
    if impl == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"aggregate_impl='pallas' compiles for TPU, but the backend is "
            f"{jax.default_backend()!r}: use 'pallas_interpret' or 'ref'")
    return impl


def aggregate_stacked(stacked, w, *, impl: str = "ref", segment_ids=None,
                      num_segments: Optional[int] = None, out_dtype=None):
    """THE routed Eq. 1 reduce: Σ_i w_i · leaf[i] over the stacked axis,
    flat (→ ``[...]``) or per-segment (→ ``[G, ...]`` local partials, the
    ``topology.segment_sum_stacked`` contract).  ``w`` is applied AS-IS —
    under ``shard_map`` the coefficients are normalized GLOBALLY and each
    shard reduces its local rows before the fleet psum, so no impl may
    renormalize here.

    ``impl="ref"`` is bitwise the pre-existing jnp lowering
    (``weighted_sum_stacked`` / ``segment_sum_stacked``);
    ``"pallas"``/``"pallas_interpret"`` route to the one-pass fused kernel
    (``kernels.fused_aggregation``, preweighted mode), f32-accumulated to
    float tolerance of the reference.  Both fused engines and the two-tier
    topology path call this for every per-round reduce, so one static
    ``aggregate_impl`` knob (engine constructor / ``FederatedALConfig``)
    swaps the lowering without any new dispatches."""
    impl = resolve_aggregate_impl(impl)
    if impl == "ref":
        if segment_ids is None:
            return weighted_sum_stacked(stacked, w, out_dtype=out_dtype)
        from repro.core.topology import segment_sum_stacked
        return segment_sum_stacked(stacked, w, segment_ids, num_segments,
                                   out_dtype=out_dtype)
    from repro.kernels.fused_aggregation import fused_aggregate
    return fused_aggregate(
        stacked, w, normalize=False, segment_ids=segment_ids,
        num_segments=num_segments, out_dtype=out_dtype,
        interpret=impl == "pallas_interpret")


def weighted_average_stacked(stacked, weights, *, mask=None,
                             exclude: Optional[Callable[[str], bool]] = None):
    """Eq. 1 directly on ``[D, ...]`` stacked params: normalize (mask-aware,
    zero-sum guarded) then reduce the device axis — one fused reduction per
    leaf, no per-device dispatches."""
    return weighted_sum_stacked(stacked, normalize_weights(weights, mask),
                                exclude=exclude)


def fedavg_stacked(stacked, *, mask=None,
                   exclude: Optional[Callable[[str], bool]] = None):
    """Uniform federated averaging over the stacked device axis (optionally
    restricted to the ``mask`` participants)."""
    D = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return weighted_average_stacked(stacked, jnp.ones((D,), jnp.float32),
                                    mask=mask, exclude=exclude)


def opt_model_stacked(stacked, scores, *, mask=None):
    """'Best-trained model' on stacked params: argmax of (masked) scores,
    returned as ``(params_of_best, best_index)``; traced-friendly (the index
    is a traced scalar, the gather is one dynamic slice per leaf)."""
    s = jnp.asarray(scores, jnp.float32)
    if mask is not None:
        s = jnp.where(jnp.asarray(mask, bool), s, -jnp.inf)
    best = jnp.argmax(s)
    return jax.tree_util.tree_map(
        lambda l: jnp.take(l, best, axis=0), stacked), best


def stacked_accuracy(eval_logits_fn, stacked_params, x, y) -> jax.Array:
    """Per-device validation accuracy ``[D]`` in ONE vmapped forward pass —
    replaces the fog node's D separate ``trainer.accuracy`` dispatches."""
    preds = jax.vmap(lambda p: jnp.argmax(eval_logits_fn(p, x), -1))(
        stacked_params)                                   # [D, N]
    return jnp.mean((preds == y[None, :]).astype(jnp.float32), axis=1)


def stack_models(models: Sequence):
    """Stack device models along a new leading axis (paper's 'stacking the
    weights by decomposition' — useful for ensembling / later analysis)."""
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *models)


def unstack_models(stacked) -> List:
    """Inverse of ``stack_models``: ``[D, ...]`` pytree → list of D pytrees."""
    D = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return [jax.tree_util.tree_map(lambda a: a[d], stacked) for d in range(D)]


def ensemble_logits(apply_fn, stacked_params, x):
    """Ensemble prediction from stacked models: mean of per-model probs."""
    logits = jax.vmap(lambda p: apply_fn(p, x))(stacked_params)  # [M, N, C]
    return jax.nn.logsumexp(jax.nn.log_softmax(logits, -1), axis=0) - jnp.log(logits.shape[0])
