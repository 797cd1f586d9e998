"""Compile-once vectorized federated-AL engine (paper Algorithm 1, batched).

The legacy driver runs Algorithm 1 as a Python nest — for each device, for
each acquisition: draw window → MC-dropout score → top-k → retrain — which
costs O(devices × acquisitions × train_steps) host→device dispatches of tiny
XLA programs.  On edge-scale simulations (the ROADMAP's "thousands of
devices") dispatch overhead dwarfs compute.

This engine runs ONE full round for ALL devices as a single compiled
program:

  * the per-device acquisition step is a pure function over fixed-shape
    state (``VPool`` masked pool + params + opt state + PRNG key);
  * the R acquisitions chain through ``jax.lax.scan``;
  * the device axis is ``jax.vmap``-ed over stacked data/state;
  * the whole thing is ``jax.jit``-ed with donated state buffers,
    so a round is exactly one dispatch regardless of D, R, or train steps.

Scoring routes through the fused Pallas kernel
(``kernels.acquisition_scores``) when the acquisition function is one of the
paper's three (entropy / BALD / VR): one VMEM-resident pass instead of three
HBM sweeps over the [T, W, C] log-prob tensor.  On CPU the default is the
pure-jnp oracle (same math, XLA-fused); ``scorer="pallas_interpret"`` forces
the kernel in interpret mode for parity testing inside the loop.

Two extensions take the engine from "one dispatch per device round" to
"massively distributed" scale (paper §IV's many-devices/few-labels regime):

  * ``run_rounds_fused`` compiles the FOG NODE into the program: whole
    rounds — device AL, per-device validation accuracy (one vmapped pass),
    Eq. 1 aggregation with participation-mask-aware weights, and re-dispatch
    of the aggregated model — chain through an outer ``lax.scan``, so T
    rounds over D devices cost ONE dispatch total.  The old path (unstack
    [D, ...] params into D pytrees, D accuracy dispatches, host-side
    average) left an O(D) Python tail per round that dwarfed the round
    itself at D ≥ 256 (measured in ``benchmarks/edge_loop_bench.py``).
  * ``EdgeEngine(..., mesh=...)`` shards the device axis across a JAX mesh
    via ``shard_map`` (``launch.mesh.make_device_mesh``): each accelerator
    simulates D/shards devices; the fused aggregation turns into
    all_gather of [D] scalars + a local weighted partial sum + one psum.
    On CPU, test with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

A third execution mode drops the round barrier entirely:
``EdgeEngine.run_async`` (``core.async_engine``) runs a continuous-time
FedAsync/FedBuff event loop — per-device completion latencies, fog
aggregation on a quorum-of-K or timer — still as one compiled dispatch.

The legacy per-device path survives behind ``EdgeEngine.run_round_legacy``
(same step function, eagerly dispatched per device per acquisition) for
equivalence testing and as the benchmark baseline.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import acquisition as acq
from repro.core import aggregation as agg_mod
from repro.core import comms as comms_mod
from repro.core import counters, vpool
from repro.core import faults as faults_mod
from repro.core import hetero as hetero_mod
from repro.core import topology as topo_mod
from repro.kernels.acquisition_scores import acquisition_scores_fused
from repro.launch.mesh import DEVICE_AXIS, FOG_AXIS

_AGGREGATIONS = ("average", "weighted", "optimal", "fedavg_n")

_FUSED_SCORES = ("entropy", "bald", "vr")

# Compiled round/step programs keyed by their full static configuration
# (see EdgeEngine._cache_key): repeated run_federated_round calls — sweeps,
# repeats, tests — with an equal config and fleet shape reuse the XLA
# executable instead of re-tracing and re-compiling per call.
_COMPILED_CACHE: dict = {}


def _compiled(key, build):
    fn = _COMPILED_CACHE.get(key)
    if fn is None:
        fn = _COMPILED_CACHE[key] = build()
    return fn


def fleet_axes(mesh) -> Optional[tuple]:
    """Mesh axis names the [D] fleet axis shards over, fog-major, or None
    off-mesh.  ``("fog", "device")`` on a 2-D hierarchical mesh
    (``launch.mesh.make_fog_mesh``), ``("device",)`` on the classic 1-D
    mesh — the single source the fused engines derive their gather/local
    slicing, psum reductions, and PartitionSpecs from."""
    if mesh is None:
        return None
    return tuple(a for a in (FOG_AXIS, DEVICE_AXIS) if a in mesh.axis_names)


def fleet_shards(mesh) -> int:
    """Total shard count of the fleet axis (product over fleet mesh axes)."""
    axes = fleet_axes(mesh)
    if not axes:
        return 1
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fleet_spec(mesh, *leading) -> P:
    """PartitionSpec placing the fleet axes on the dim after ``leading``
    entries: ``_fleet_spec(mesh)`` shards dim 0, ``_fleet_spec(mesh, None)``
    dim 1 (per-round [T, D] rows) — a tuple entry on 2-D meshes."""
    axes = fleet_axes(mesh)
    entry = axes[0] if len(axes) == 1 else axes
    return P(*leading, entry)


def _fleet_collectives(mesh, D: int):
    """(gather, local, psum) closures over the fleet mesh axes.

    ``gather`` reassembles a global [D, ...] from this shard's local rows
    (all_gather minor axis first, so the concatenation order matches the
    fog-major layout of ``_fleet_spec``); ``local`` slices this shard's
    rows back out of a replicated global; ``psum`` sums partials over every
    fleet axis (group-local psum over "device" + fog-axis psum over "fog"
    on the 2-D mesh).  Off-mesh all three are identities."""
    axes = fleet_axes(mesh)
    if not axes:
        return (lambda v: v), (lambda v: v), (lambda v: v)
    D_local = D // fleet_shards(mesh)

    def gather(v):
        for a in reversed(axes):
            v = jax.lax.all_gather(v, a, tiled=True)
        return v

    def local(v):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return jax.lax.dynamic_slice_in_dim(v, idx * D_local, D_local, axis=0)

    def psum(x):
        return jax.lax.psum(x, axes if len(axes) > 1 else axes[0])

    return gather, local, psum


class EngineState(NamedTuple):
    """Per-device state, stacked along a leading device axis D.

    ``residual`` is the comms error-feedback buffer (``[D, ...]`` pytree
    mirroring ``params``), populated only by ``run_rounds_fused`` when a
    lossy ``CommsConfig`` with ``error_feedback`` is active; it defaults to
    an empty pytree so every other path ignores it at zero cost.

    ``pending`` / ``staleness`` are the heterogeneous-fleet buffers
    (``core.hetero``), populated only when a ``HeteroConfig`` is active:
    ``pending`` holds each straggler's not-yet-delivered delta (a
    ``[D, ...]`` mirror of params), ``staleness`` its age in rounds
    (``[D] int32``).  Like ``residual`` they default to empty pytrees and
    shard over the device mesh axis.

    ``live`` is the churn liveness vector (``core.faults``): ``[D]`` 0/1
    float, populated only when a fault/churn config is active.  Dead slots
    are bitwise inert — their pools, pending backlogs, residuals, and
    staleness counters freeze, and Eq. 1 weights normalize over live
    arrivals only."""
    params: Any          # [D, ...] pytree
    opt_state: Any       # [D, ...] pytree
    pool: vpool.VPool    # [D, ...] fields
    rng: jax.Array       # [D] PRNG keys
    residual: Any = ()   # [D, ...] pytree (comms error feedback) or ()
    pending: Any = ()    # [D, ...] pytree (buffered straggler deltas) or ()
    staleness: Any = ()  # [D] int32 staleness counters or ()
    live: Any = ()       # [D] float32 churn liveness (1 = live) or ()


def stack_device_data(device_data: Sequence):
    """Pad ragged device shards to a common length and stack.

    Returns ``(images [D, n_pad, ...], labels [D, n_pad], valid [D, n_pad])``.
    Padding slots are marked invalid and are born "labeled" in the pool so
    the window draw can never select them.
    """
    D = len(device_data)
    n_pad = max(len(d) for d in device_data)
    img_shape = device_data[0].images.shape[1:]
    # dtype-preserving: float32 images for the paper's LeNet, int32 token
    # sequences for the LM adapters — the engine is sample-modality-agnostic
    img_dtype = np.asarray(device_data[0].images).dtype
    images = np.zeros((D, n_pad) + img_shape, img_dtype)
    labels = np.zeros((D, n_pad), np.int32)
    valid = np.zeros((D, n_pad), bool)
    for i, d in enumerate(device_data):
        n = len(d)
        images[i, :n] = d.images
        labels[i, :n] = d.labels
        valid[i, :n] = True
    return jnp.asarray(images), jnp.asarray(labels), jnp.asarray(valid)


def resolve_scorer(mode: str) -> str:
    """``auto`` → the fused Pallas kernel on TPU, the jnp oracle elsewhere.
    An explicit ``"pallas"`` off-TPU raises: the kernel would otherwise run
    in interpret mode, which only ``"pallas_interpret"`` asks for."""
    if mode in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if mode == "pallas" and jax.default_backend() != "tpu":
        raise ValueError(
            f"scorer='pallas' compiles for TPU, but the backend is "
            f"{jax.default_backend()!r}: use 'pallas_interpret' or 'jnp'")
    return mode


def _make_score_fn(acquisition_fn: str, scorer: str):
    """logp [T, W, C] → scores [W]; higher = more informative."""
    scorer = resolve_scorer(scorer)
    if scorer in ("pallas", "pallas_interpret") and acquisition_fn in _FUSED_SCORES:
        interpret = scorer == "pallas_interpret"

        def score(logp):
            ent, bald, vr = acquisition_scores_fused(logp, interpret=interpret)
            return {"entropy": ent, "bald": bald, "vr": vr}[acquisition_fn]

        return score
    return lambda logp: acq.acquisition_scores(acquisition_fn, logp)


class EdgeEngine:
    """Vectorized round executor over a fixed device fleet.

    Built once per (config, fleet) pair; the compiled round program is cached
    across rounds (compile-once discipline: padding + masking + donation keep
    every shape static as labels accumulate).
    """

    def __init__(self, trainer, cfg, device_data: Sequence, seed_data,
                 test_set=None, *, total_acquisitions: Optional[int] = None,
                 scorer: Optional[str] = None, unroll: Optional[bool] = None,
                 aggregate_impl: Optional[str] = None, mesh=None):
        self.trainer = trainer
        self.cfg = cfg
        self.mesh = mesh
        if mesh is not None:
            if DEVICE_AXIS not in mesh.axis_names:
                raise ValueError(
                    f"mesh must carry a {DEVICE_AXIS!r} axis "
                    f"(launch.mesh.make_device_mesh / make_fog_mesh); "
                    f"got {mesh.axis_names}")
            shards = fleet_shards(mesh)
            if len(device_data) % shards:
                raise ValueError(
                    f"num_devices={len(device_data)} must divide evenly over "
                    f"the {shards}-way fleet mesh "
                    f"{tuple(fleet_axes(mesh))}")
        # XLA:CPU loses intra-op threading inside while-loop bodies (~3x on
        # the conv train step), so on CPU both scans are unrolled into a
        # straight-line program; on TPU the rolled while-loop compiles faster
        # and runs at full speed.
        self.unroll = (jax.default_backend() == "cpu") if unroll is None else unroll
        self.num_devices = len(device_data)
        self.images, self.labels, self.valid = stack_device_data(device_data)
        if mesh is not None:
            # commit the fleet data to its shards once, not per dispatch
            sharding = NamedSharding(mesh, _fleet_spec(mesh))
            self.images = jax.device_put(self.images, sharding)
            self.labels = jax.device_put(self.labels, sharding)
            self.valid = jax.device_put(self.valid, sharding)
        n_pad = self.images.shape[1]
        self.window = min(cfg.pool_window, n_pad)
        self.k = min(cfg.k_per_acquisition, self.window)
        self.capacity = (total_acquisitions or cfg.acquisitions) * self.k
        self.scorer = resolve_scorer(scorer if scorer is not None
                                     else getattr(cfg, "scorer", "auto"))
        self._score_fn = _make_score_fn(cfg.acquisition_fn, self.scorer)
        # Eq. 1 reduce lowering (aggregation.aggregate_stacked): "ref" is
        # the jnp program, "pallas" the fused one-pass kernel; resolved
        # here so it is a static fact of the engine (and its jit cache key)
        self.aggregate_impl = agg_mod.resolve_aggregate_impl(
            aggregate_impl if aggregate_impl is not None
            else getattr(cfg, "aggregate_impl", "auto"))

        if seed_data is not None and len(seed_data) > 0:
            self.seed_images = jnp.asarray(seed_data.images)
            self.seed_labels = jnp.asarray(seed_data.labels.astype(np.int32))
        else:
            img_shape = self.images.shape[2:]
            self.seed_images = jnp.zeros((0,) + img_shape, self.images.dtype)
            self.seed_labels = jnp.zeros((0,), jnp.int32)
        if test_set is not None and len(test_set) > 0:
            self.test_images = jnp.asarray(test_set.images)
            self.test_labels = jnp.asarray(test_set.labels.astype(np.int32))
        else:
            self.test_images = None
            self.test_labels = None

    # ------------------------------------------------------------ state
    def device_keys(self, round_idx: int = 0) -> jax.Array:
        """Mirrors the legacy driver's per-device key schedule.  Vectorized
        (vmapped key construction is bit-identical to the Python loop) so a
        D=1024 fleet doesn't pay 1024 tiny host dispatches per round."""
        cfg = self.cfg
        return jax.vmap(lambda d: jax.random.key(
            cfg.seed + 7919 * (d + 1) + 104729 * round_idx))(
                jnp.arange(self.num_devices))

    def _num_classes(self) -> int:
        """Label vocabulary size (the label-noise redraw bound)."""
        if getattr(self.trainer, "num_classes", None) is not None:
            return int(self.trainer.num_classes)
        return int(getattr(getattr(self.trainer, "model_cfg", None),
                           "num_classes", 10))

    def _exclude_paths(self, params) -> tuple:
        """Static tuple of flat leaf paths the trainer's adapter keeps OUT
        of Eq. 1 (per-device recurrent state — ``ModelAdapter
        .aggregate_mask``).  Empty for adapter-less trainers and for LeNet:
        the fused programs then take exactly the pre-adapter code path."""
        adapter = getattr(self.trainer, "adapter", None)
        if adapter is None:
            return ()
        from repro.core.model_adapter import excluded_paths
        return excluded_paths(adapter, params)

    def _shard_state(self, state: EngineState) -> EngineState:
        if self.mesh is None:
            return state
        from repro.launch.sharding import shard_engine_state
        return shard_engine_state(self.mesh, state)

    def init_state(self, params0, *, round_idx: int = 0) -> EngineState:
        D = self.num_devices
        params = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (D,) + a.shape), params0)
        opt_state = self.trainer.opt.init(params)
        pool = vpool.VPool(
            labeled_mask=~self.valid,
            labeled_idx=jnp.full((D, self.capacity), -1, jnp.int32),
            labeled_valid=jnp.zeros((D, self.capacity), bool),
            n_filled=jnp.zeros((D,), jnp.int32),
        )
        return self._shard_state(
            EngineState(params, opt_state, pool, self.device_keys(round_idx)))

    def set_params(self, state: EngineState, params0, *,
                   round_idx: int = 0) -> EngineState:
        """Re-dispatch an aggregated model to the fleet (pools persist,
        optimizer state and keys reset — same protocol as the legacy loop)."""
        D = self.num_devices
        params = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (D,) + a.shape), params0)
        return self._shard_state(
            EngineState(params, self.trainer.opt.init(params), state.pool,
                        self.device_keys(round_idx), state.residual,
                        state.pending, state.staleness, state.live))

    def resume_state(self, state: EngineState, *,
                     next_round: int) -> EngineState:
        """Re-key a restored checkpoint for continuation.

        The fused engines take round-t keys from the precomputed schedule
        (``device_keys`` at ABSOLUTE round indices) and DISCARD the evolved
        carry rng, so a checkpointed ``state.rng`` is one round stale:
        resuming with it would replay the interrupted round's randomness.
        This installs the key the uninterrupted run would have used for
        ``next_round`` (= rounds/events completed so far) and re-commits the
        state to the mesh shards; pass the same value as ``start_round`` /
        ``start_event`` on the continuation call and the resumed run is
        bit-for-bit the uninterrupted one (asserted in
        ``tests/test_faults.py``)."""
        return self._shard_state(
            state._replace(rng=self.device_keys(next_round)))

    def device_params_list(self, state: EngineState) -> List:
        return agg_mod.unstack_models(state.params)

    def labeled_counts(self, state: EngineState) -> List[int]:
        """Per-device labeled-sample counts n_i (the fedavg_n / Eq. 1
        weights) — the single source the host aggregation path, benchmarks,
        and tests share."""
        return [int(n) for n in
                np.asarray(jax.vmap(vpool.n_labeled)(state.pool))]

    # ------------------------------------------------------------ the step
    def _acquisition_step(self, record_curves: bool):
        """One acquisition for ONE device as a pure function — the unit that
        is scanned over R and vmapped over D.  All data (device shard, seed
        set, test set) arrives as traced arguments so the compiled program is
        reusable across same-shaped fleets (see ``_compiled``)."""
        cfg, trainer = self.cfg, self.trainer
        W, k, T = self.window, self.k, cfg.mc_samples
        steps = cfg.train_steps_per_acq
        score_fn = self._score_fn
        # locals only below — capturing self would pin the engine's stacked
        # fleet arrays inside the process-lifetime _COMPILED_CACHE
        train_unroll = steps if self.unroll else 1

        def step(carry, images_d, labels_d, seed_x, seed_y, test_x, test_y,
                 steps_d=None):
            # ``steps_d`` (traced per-device scalar, optional) is the hetero
            # compute profile: local fit steps past it are masked out inside
            # fit_steps_raw, so slow devices contribute less-trained work
            # without breaking the static round shape.
            params, opt_state, pool, rng = carry
            rng, k_draw, k_score, k_sel, k_fit = jax.random.split(rng, 5)

            win_idx, win_valid = vpool.draw_window(pool, k_draw, W)
            if cfg.acquisition_fn == "random":
                scores = jax.random.uniform(k_sel, (W,))
            else:
                x_win = jnp.take(images_d, win_idx, axis=0)
                logp = trainer.score_logprobs_raw(params, x_win, k_score, T)
                scores = score_fn(logp)
            scores = jnp.where(win_valid, scores, -jnp.inf)
            sel = jax.lax.top_k(scores, k)[1]
            sel_valid = jnp.take(win_valid, sel)
            pool = vpool.acquire(pool, win_idx, sel, sel_valid)

            # fixed-capacity masked training set: seed ∪ acquired
            gidx = jnp.clip(pool.labeled_idx, 0)
            x = jnp.concatenate([seed_x, jnp.take(images_d, gidx, axis=0)])
            y = jnp.concatenate([seed_y, jnp.take(labels_d, gidx)])
            m = jnp.concatenate([jnp.ones((seed_x.shape[0],), jnp.float32),
                                 pool.labeled_valid.astype(jnp.float32)])
            params, opt_state = trainer.fit_steps_raw(
                params, opt_state, x, y, m, k_fit, steps,
                unroll=train_unroll, step_limit=steps_d)

            rec = {
                "n_labeled": vpool.n_labeled(pool),
                "selected": jnp.where(sel_valid, jnp.take(win_idx, sel), -1),
            }
            if record_curves:
                preds = jnp.argmax(trainer.eval_logits_raw(params, test_x), -1)
                rec["test_acc"] = jnp.mean((preds == test_y).astype(jnp.float32))
            return (params, opt_state, pool, rng), rec

        return step

    def _cache_key(self, kind: str, record: bool):
        """Compiled programs depend only on this tuple: the math is fully
        determined by (trainer class + its configs, AL config) and the static
        shapes; a fresh same-config EdgeEngine can reuse a cached program.
        ``seed`` never enters the traced program (PRNG keys arrive via the
        state argument), so it is normalized out — seed sweeps and
        ``run_experiment`` repeats hit the same executable."""
        from dataclasses import replace as _replace

        def _no_seed(c):
            try:
                return _replace(c, seed=0)
            except (TypeError, ValueError):
                return c

        return (kind, type(self.trainer),
                # adapter identity subsumes model_cfg when present (frozen
                # dataclass — hashable); legacy trainers fall back to the
                # raw model config slot unchanged
                getattr(self.trainer, "adapter",
                        getattr(self.trainer, "model_cfg", None)),
                _no_seed(getattr(self.trainer, "cfg", None)),
                _no_seed(self.cfg),
                self.images.shape, self.capacity, self.window, self.k,
                self.scorer, self.aggregate_impl, self.unroll,
                self.seed_images.shape,
                None if self.test_images is None else self.test_images.shape,
                record, self.mesh)

    def _get_round_jit(self, record_curves: bool):
        def build():
            step = self._acquisition_step(record_curves)
            R = self.cfg.acquisitions
            round_unroll = R if self.unroll else 1  # local: no self in closure
            mesh = self.mesh

            def round_all(state, images, labels, seed_x, seed_y,
                          test_x=None, test_y=None):
                def device_round(carry, images_d, labels_d):
                    return jax.lax.scan(
                        lambda c, _: step(c, images_d, labels_d, seed_x,
                                          seed_y, test_x, test_y),
                        carry, None, length=R, unroll=round_unroll)

                carry = (state.params, state.opt_state, state.pool, state.rng)
                carry, recs = jax.vmap(device_round)(carry, images, labels)
                return EngineState(*carry), recs

            if mesh is not None:
                # Shard the device axis: each mesh shard vmaps its D/shards
                # local devices; no collectives needed for a plain round.
                dev = _fleet_spec(mesh)
                n_extra = 4 if record_curves else 2
                round_all = jax.shard_map(
                    round_all, mesh=mesh,
                    in_specs=(dev, dev, dev) + (P(),) * n_extra,
                    out_specs=(dev, dev), check_vma=False)

            from repro.core.federated import _donate_argnums
            return jax.jit(round_all, donate_argnums=_donate_argnums(0))

        return _compiled(self._cache_key("round", record_curves), build)

    def _get_step_jit(self, record_curves: bool):
        def build():
            step = self._acquisition_step(record_curves)
            return jax.jit(
                lambda carry, images_d, labels_d, seed_x, seed_y,
                test_x=None, test_y=None: step(carry, images_d, labels_d,
                                               seed_x, seed_y, test_x, test_y))

        return _compiled(self._cache_key("step", record_curves), build)

    def _data_args(self, record: bool):
        args = (self.seed_images, self.seed_labels)
        if record:
            args += (self.test_images, self.test_labels)
        return args

    def _check_capacity(self, state: EngineState, *, rounds: int = 1,
                        extra_per_round: int = 0):
        """A round appends R·k slots per device (plus ``extra_per_round``
        — stream escalations); dynamic_update_slice would silently
        clamp-and-overwrite past capacity, so fail loudly instead.
        Size the pool with ``total_acquisitions`` for multi-round use."""
        need = int(np.max(np.asarray(state.pool.n_filled))) \
            + rounds * (self.cfg.acquisitions * self.k + extra_per_round)
        if need > self.capacity:
            raise ValueError(
                f"pool capacity {self.capacity} cannot absorb {rounds} "
                f"round(s) (would need {need} slots); construct EdgeEngine "
                f"with total_acquisitions covering all rounds")

    # ----------------------------------------------------- fused fog rounds
    def _get_rounds_fused_jit(self, rounds: int, aggregation: str,
                              mask_mode: str, comms_key=None,
                              hetero_key=None, faults_key=None,
                              guards_key=None, churn_mode: str = "none",
                              topo_key=None, excl_paths: tuple = ()):
        """T whole rounds — device AL + Eq. 1 aggregation + re-dispatch — as
        ONE compiled program (an outer scan over rounds).

        ``mask_mode``:
          * ``"given"``     — participation mask arrives as a traced
            ``[rounds, D]`` float array (1 = uploaded);
          * ``"bernoulli"`` — the mask is DRAWN INSIDE the program,
            Bernoulli(upload_fraction) per device per round from a
            per-round key (the paper's §III-B asynchronization tolerance
            as a traced knob — the fraction is a traced scalar, so sweeping
            it reuses the executable).

        Weights are normalized over actual participants
        (``aggregation.normalize_weights``): a device that skipped the round
        contributes nothing, zero-weight-sum rounds fall back to uniform.

        ``comms_key`` is the static ``(compression, topk_fraction,
        error_feedback, compute_dtype)`` tuple (or None): with a lossy
        wire — a real codec, or a bf16 ``compute_dtype`` rounding the
        upload values to the 2-byte width — the round compresses
        per-device DELTAS w_i − w_dispatched (plus the carried
        error-feedback residual) inside the program and aggregates
        BASE + Σ αᵢ·C(Δᵢ + eᵢ) — exact for C = identity because Σα = 1 —
        so compressed rounds stay one dispatch and shard unchanged (the
        codec is per-device-local; only the weighted delta sum is psum'd).
        Every Eq. 1 reduce routes through ``aggregation
        .aggregate_stacked`` with the engine's static ``aggregate_impl``
        (``"ref"`` = the jnp program below, ``"pallas"`` = the fused
        one-pass kernel in ``kernels.fused_aggregation``, preweighted
        mode — local rows reduce with the GLOBAL coefficients, partials
        psum'd, so the kernel never renormalizes under the mesh).

        ``hetero_key`` is the static ``(decay, decay_rate, buffer_stale,
        use_step_limits)`` tuple (or None) from a ``core.hetero
        .HeteroConfig``.  With it, the mask becomes an ARRIVAL mask with
        straggler-tolerant semantics: a missing device's delta is buffered
        in the carried ``pending`` pytree (not discarded), its ``staleness``
        counter increments, and on arrival the backlog folds into the upload
        weighted by ``alpha_i ∝ raw_i · decay(staleness_i)``
        (``aggregation.staleness_weights``).  The hetero path always
        aggregates in delta form (BASE + Σ αᵢ·uᵢ — exact because Σα = 1),
        composes with the comms codecs (the codec compresses the whole
        backlog-bearing upload) and with the step-limit compute profile
        (per-device traced fit budgets), and shards unchanged: staleness is
        one more all_gather'd [D] scalar, pending is device-local state.

        ``faults_key`` / ``guards_key`` / ``churn_mode`` are the
        fault-tolerance statics (``core.faults``): ``faults_key`` is
        ``(corrupt_mode, num_classes)`` or None — every fault RATE is
        traced (one ``[N_RATES]`` vector argument), so rate sweeps reuse
        the executable; ``guards_key`` is the guard policy (``"drop"`` /
        ``"clip"``) or None, with the outlier ``norm_factor`` traced;
        ``churn_mode`` selects where liveness comes from: ``"given"`` (a
        ``[rounds, D]`` host schedule in the xs), ``"process"`` (the
        in-trace birth/death chain carried in ``state.live``), or
        ``"none"``.  With any of them active the round aggregates in DELTA
        form (exact because Σα = 1): uploads are masked to live,
        non-crashed senders; dropped uploads vanish fog-side; wire
        corruption hits the received delta AFTER the error-feedback
        residual update (the device-side EF buffer stays clean); the guard
        verdict zeroes or clips rejected uploads and the Eq. 1 weights
        renormalize over the ACCEPTED arrivals, an all-rejected round
        keeping the previous fog model.  With all three off the emitted
        program is the unchanged pre-fault one.

        ``topo_key`` is the hierarchical-fog static tuple ``(num_groups,
        local_steps, fog_compression, has_compute_profile)`` (or None =
        flat fleet) from a ``core.topology.FogTopology``.  With it the
        round carries [G, ...] fog models and aggregates in TWO Eq. 1
        levels: intra-fog (per-group masked normalization + segment sums
        over the stacked axis) every round, inter-fog (β over group
        arrival masses) only on sync rounds (the traced ``sync_flags`` xs
        row — between syncs nothing crosses the fog→cloud tier and each
        device is re-dispatched its own group's fog model).  A group with
        no accepted arrivals keeps its previous fog model (a dead fog
        group is all its slots dark).  Because β_g is each group's share
        of the total arrival mass, the sync-round global is the FLAT
        Eq. 1 model — G=1/local_steps=1 reduces bitwise to the flat
        program.  ``fog_compression`` optionally runs a second codec on
        the fog→cloud link (the per-group delta sums, vmapped over G).

        ``excl_paths`` is the adapter's static tuple of flat leaf paths
        excluded from Eq. 1 (``model_adapter.excluded_paths``): excluded
        leaves — per-device recurrent/SSM state — carry no upload mass,
        survive re-dispatch with each device's OWN value, and the
        returned fog model reports the GLOBAL slot-0 device's copy
        (one-hot representative + fleet psum — mesh-exact, unlike the
        shard-local ``leaf[0]`` caveat in
        ``aggregation.weighted_sum_stacked``).  Empty tuple (every
        adapter-free call) emits the unchanged pre-adapter program.
        """

        def build():
            # comms_key is only non-None when the wire is lossy: a real
            # codec OR a sub-f32 compute_dtype (bf16 rounding is itself a
            # codec — identity at fraction 1.0 it is not)
            compress = comms_key is not None
            use_ef = compress and comms_key[2]
            cc = (comms_mod.CommsConfig(compression=comms_key[0],
                                        topk_fraction=comms_key[1],
                                        error_feedback=comms_key[2],
                                        compute_dtype=comms_key[3])
                  if compress else None)
            agg_impl = self.aggregate_impl
            hetero_on = hetero_key is not None
            if hetero_on:
                h_decay, h_rate, h_buffer, h_steps = hetero_key
            else:
                h_decay, h_rate, h_buffer, h_steps = "none", 1.0, False, False
            faults_on = faults_key is not None
            guards_on = guards_key is not None
            churn_on = churn_mode != "none"
            fault_like = faults_on or guards_on or churn_on
            topo_on = topo_key is not None
            if topo_on:
                G, t_steps, fog_comp, topo_steps = topo_key
                fog_local = t_steps > 1     # any non-sync rounds at all?
                fog_compress = fog_comp != "none"
                fog_cc = (comms_mod.CommsConfig(compression=fog_comp)
                          if fog_compress else None)
            else:
                G, fog_local, fog_compress, fog_cc, topo_steps = (
                    1, False, False, None, False)
            # faults and guards need the per-device upload tree explicitly
            # (to corrupt / norm-check / zero it), so they force the exact
            # delta-form aggregation even without a codec; the fog-tier
            # codec compresses per-group DELTA sums, so it does too
            delta_form_always = (compress or faults_on or guards_on
                                 or fog_compress)
            use_steps = h_steps or topo_steps
            if faults_on:
                corrupt_mode, num_classes = faults_key
            step = self._acquisition_step(False)
            R = self.cfg.acquisitions
            round_unroll = R if self.unroll else 1
            has_val = self.test_images is not None
            mesh = self.mesh
            on_mesh = mesh is not None
            D = self.num_devices
            D_local = D // fleet_shards(mesh)
            trainer = self.trainer
            eval_fn = trainer.eval_logits_raw
            tmap = jax.tree_util.tree_map
            # local [D_local] scalar ↔ global [D] and the fleet psum —
            # identities off-mesh, fog-major 2-D aware on a fog mesh
            gather, local, fpsum = _fleet_collectives(mesh, D)
            # adapter-excluded leaves (per-device recurrent state, out of
            # Eq. 1); everything below is gated on has_excl so the empty
            # tuple emits the unchanged pre-adapter program
            has_excl = bool(excl_paths)
            excl_set = frozenset(excl_paths)
            twp = jax.tree_util.tree_map_with_path

            def _is_excl(kp):
                return agg_mod._path_str(kp) in excl_set

            def _zero_excluded(tree):
                # excluded leaves carry no Eq. 1 mass: zeroing them out of
                # the upload deltas keeps EF residuals, guard norms, byte
                # accounting, and both fog tiers free of per-device state
                return twp(lambda kp, a: (jnp.zeros_like(a) if _is_excl(kp)
                                          else a), tree)

            def _keep_excluded(trained, dispatched):
                # re-dispatch select: excluded leaves keep each device's
                # OWN trained value, the rest take the fog model
                return twp(lambda kp, t, d: t if _is_excl(kp) else d,
                           trained, dispatched)

            def rounds_all(state, images, labels, seed_x, seed_y,
                           val_x, val_y, keys_all, mask_arg, fraction,
                           step_limits, live_arg, fkeys, frates, gfactor,
                           group_ids, sync_flags, fog_keys):
                n_pad = labels.shape[1]
                if topo_on:
                    gid_l = local(group_ids)

                def _where_vec(vec_l, on_true, on_false):
                    # leafwise per-device select over stacked [D_local, ...]
                    return tmap(
                        lambda a, o: jnp.where(
                            vec_l.reshape(
                                (-1,) + (1,) * (a.ndim - 1)) > 0, a, o),
                        on_true, on_false)

                if has_excl:
                    # GLOBAL slot-0 representative row, mesh-exact: a bare
                    # ``leaf[0]`` under shard_map reads each shard's LOCAL
                    # device 0 (the documented caveat in
                    # aggregation.weighted_sum_stacked) — the one-hot
                    # weighting + fleet psum picks the true global slot 0
                    rep0_l = local(
                        jnp.zeros((D,), jnp.float32).at[0].set(1.0))

                    def _slot0_excluded(stacked, base):
                        # excluded leaves of ``base`` take global slot 0's
                        # row of ``stacked``; the rest pass through
                        return twp(
                            lambda kp, s, b: (fpsum(jnp.tensordot(
                                rep0_l, s, axes=1)) if _is_excl(kp) else b),
                            stacked, base)

                def one_round(carry, xs):
                    if topo_on:
                        (params, opt_state, pool, _, residual, pending,
                         staleness, live, fog) = carry
                        *xs, sync_f, fogkey = xs
                    else:
                        (params, opt_state, pool, _, residual, pending,
                         staleness, live) = carry
                    if mask_mode == "bernoulli":
                        keys_r, mask_key, live_row, fkey = xs
                        # same key on every shard → consistent global draw
                        mask_g = jax.random.bernoulli(
                            mask_key, fraction, (D,)).astype(jnp.float32)
                        mask_l = local(mask_g)
                    else:
                        keys_r, mask_l, live_row, fkey = xs
                        mask_g = gather(mask_l)

                    # ---- liveness + fault draws (one fault key per round,
                    # folded at the absolute index: sweeps and resumed runs
                    # replay the identical fault trace)
                    if faults_on or churn_mode == "process":
                        k_live, k_flt, k_labels = jax.random.split(fkey, 3)
                    live_g = None
                    if churn_mode == "given":
                        live_g = live_row          # replicated [D] xs row
                        live = local(live_g)
                    elif churn_mode == "process":
                        live_g = faults_mod.update_liveness(
                            k_live, gather(live), frates[faults_mod.RATE_DEATH],
                            frates[faults_mod.RATE_BIRTH])
                        live = local(live_g)
                    if faults_on:
                        crash_g, drop_g, corrupt_g, noise_g = \
                            faults_mod.draw_fault_masks(k_flt, frates, D)
                    # active = survived this round's local work: dead or
                    # crashed devices commit nothing and upload nothing
                    active_g = live_g
                    if faults_on:
                        crash_live_g = (crash_g if live_g is None
                                        else crash_g * live_g)
                        active_g = ((1.0 - crash_g) if active_g is None
                                    else active_g * (1.0 - crash_g))

                    # label-noise burst: the flagged device trains this round
                    # on uniformly random labels (drawn globally with one
                    # key so every mesh shard agrees, then sliced local)
                    labels_r = labels
                    if faults_on:
                        noisy_l = local(jax.random.randint(
                            k_labels, (D, n_pad), 0, num_classes,
                            dtype=labels.dtype))
                        noise_l = local(noise_g)
                        labels_r = jnp.where(noise_l[:, None] > 0,
                                             noisy_l, labels)

                    # the model every device starts this round from (all rows
                    # identical — the previous round's / init's re-dispatch);
                    # the delta paths compress/buffer against it
                    params_prev = params

                    def device_round(c, images_d, labels_d, steps_d):
                        return jax.lax.scan(
                            lambda cc, _: step(
                                cc, images_d, labels_d, seed_x, seed_y,
                                None, None,
                                steps_d if use_steps else None),
                            c, None, length=R, unroll=round_unroll)

                    (params2, opt2, pool2, rng2), _ = jax.vmap(device_round)(
                        (params, opt_state, pool, keys_r), images, labels_r,
                        step_limits)
                    if active_g is not None:
                        # dead/crashed devices lose the round: pool, params,
                        # optimizer, and key stream all stay frozen (inert)
                        active_l = local(active_g)
                        params = _where_vec(active_l, params2, params)
                        opt_state = _where_vec(active_l, opt2, opt_state)
                        pool = _where_vec(active_l, pool2, pool)
                        rng = jnp.where(active_l > 0, rng2, keys_r)
                    else:
                        params, opt_state, pool, rng = (params2, opt2,
                                                        pool2, rng2)

                    # upload_: the device transmitted; recv_: the fog node
                    # received (drops happen on the wire).  All equal to the
                    # participation mask when faults are off.
                    if active_g is not None:
                        upload_g = mask_g * active_g
                        upload_l = local(upload_g)
                    else:
                        upload_g, upload_l = mask_g, mask_l
                    recv_g = (upload_g * (1.0 - drop_g) if faults_on
                              else upload_g)

                    # ---- in-compile fog node: Eq. 1 over the stacked axis
                    counts_g = gather(
                        jax.vmap(vpool.n_labeled)(pool).astype(jnp.float32))
                    if has_val:
                        accs_g = gather(agg_mod.stacked_accuracy(
                            eval_fn, params, val_x, val_y))
                    else:
                        accs_g = jnp.zeros_like(counts_g)
                    if aggregation == "average":
                        raw = jnp.ones((D,), jnp.float32)
                    elif aggregation == "weighted":
                        raw = accs_g
                    elif aggregation == "fedavg_n":
                        raw = counts_g
                    else:  # optimal: one-hot at the best participant
                        masked = jnp.where(mask_g > 0, accs_g, -jnp.inf)
                        raw = jax.nn.one_hot(jnp.argmax(masked), D)
                    # ---- build the upload trees first: the guard verdict
                    # needs the actual deltas before weights can exist
                    backlog = None
                    if h_buffer or delta_form_always:
                        # this round's fresh work against the dispatched
                        # base, plus (hetero) the buffered backlog
                        delta = tmap(jnp.subtract, params, params_prev)
                        if has_excl:
                            delta = _zero_excluded(delta)
                        backlog = (tmap(jnp.add, delta, pending)
                                   if h_buffer else delta)
                    sent = None
                    if compress:
                        # delta-form Eq. 1 upload: C(uᵢ) with uᵢ the
                        # backlog-bearing delta plus the carried EF
                        # residual; everything stays device-local
                        to_send = (tmap(jnp.add, backlog, residual)
                                   if use_ef else backlog)
                        qkeys = jax.vmap(
                            lambda k: jax.random.fold_in(k, 0x636F6D))(rng)
                        sent = jax.vmap(
                            lambda k, d: comms_mod.compress_tree(cc, k, d))(
                                qkeys, to_send)
                        if use_ef:
                            # EF updates on actual TRANSMISSION only
                            # (Karimireddy et al.): a device masked out of
                            # this round — or dead, or crashed — sent
                            # nothing, so its residual stays frozen;
                            # overwriting it would delete error mass a REAL
                            # earlier upload still owes the fog node.  The
                            # update uses the clean ``sent``: wire
                            # corruption below is fog-side and must never
                            # leak into the device-side buffer.
                            residual = _where_vec(
                                upload_l,
                                tmap(jnp.subtract, to_send, sent), residual)
                    elif delta_form_always:
                        sent = backlog
                    if faults_on:
                        # wire corruption: received uploads only, applied
                        # AFTER the EF residual update
                        sent = faults_mod.corrupt_stacked(
                            corrupt_mode, sent, local(corrupt_g * recv_g),
                            frates[faults_mod.RATE_CORRUPT_SCALE])

                    # ---- fog-side guards: reject non-finite / norm-outlier
                    # uploads and ZERO their leaves (a 0-weight NaN still
                    # poisons a weighted sum); clip policy scales outliers
                    # back to the threshold instead
                    if guards_on:
                        norms_g = gather(faults_mod.stacked_norms(sent))
                        finite_g = gather(faults_mod.stacked_finite(sent))
                        reject_g, clip_g, scale_g = faults_mod.guard_verdict(
                            norms_g, finite_g, recv_g, policy=guards_key,
                            factor=gfactor,
                            group_ids=group_ids if topo_on else None,
                            num_groups=G if topo_on else None)
                        accept_g = recv_g * (1.0 - reject_g)
                        if guards_key == "clip":
                            scale_l = local(scale_g)
                            sent = tmap(
                                lambda a: a * scale_l.reshape(
                                    (-1,) + (1,) * (a.ndim - 1)), sent)
                        sent = _where_vec(local(accept_g), sent,
                                          tmap(jnp.zeros_like, sent))
                    else:
                        accept_g = recv_g

                    # ---- Eq. 1 weights over the ACCEPTED arrivals
                    if hetero_on:
                        # staleness-aware Eq. 1: arrivals weighted by
                        # raw_i · decay(age of their backlog)
                        stale_g = gather(staleness)
                        decayed = raw * agg_mod.staleness_decay(
                            stale_g, kind=h_decay, rate=h_rate)
                    else:
                        decayed = raw
                    w_g = agg_mod.masked_normalize(decayed, accept_g)
                    if topo_on:
                        # both Eq. 1 levels' coefficients: intra-fog alpha
                        # (per-group normalization of the SAME decayed
                        # basis) and inter-fog beta (group arrival-mass
                        # shares, so alpha·beta is the flat weight)
                        alpha, beta, group_any = topo_mod.two_tier_weights(
                            decayed, accept_g, group_ids, G)
                        accept_any = jnp.sum(accept_g) > 0
                    if hetero_on or fault_like:
                        # a zero-accept round aggregates NOTHING: the
                        # no-participant uniform fallback of
                        # normalize_weights would aggregate unweighted
                        # garbage (and, for buffering hetero, fold every
                        # device's banked backlog in now AND re-bank it —
                        # the upload-0 pending branch — double-applying
                        # each delta on its real arrival).  Zero the
                        # weights and keep the previous fog model instead
                        # (guard below).
                        accept_any = jnp.sum(accept_g) > 0
                        w_g = jnp.where(accept_any, w_g,
                                        jnp.zeros_like(w_g))

                    fog_delta = None
                    if delta_form_always:
                        # delta-form Eq. 1: BASE + Σ αᵢ·uᵢ (exact for
                        # C = identity and no faults because Σα = 1); only
                        # the weighted sum is psum'd
                        agg = fpsum(agg_mod.aggregate_stacked(
                            sent, local(w_g), impl=agg_impl))
                        if topo_on:
                            # inter-fog delta form: Σ_g β_g·F_g is the
                            # sync base (β ≡ 1.0 at G=1, so this is the
                            # flat BASE bitwise); the flat weighted delta
                            # sum rides on top unless the fog-tier codec
                            # compresses the per-group delta sums first
                            base = topo_mod.group_reduce_stacked(fog, beta)
                            if fog_compress or fog_local:
                                fog_delta = fpsum(agg_mod.aggregate_stacked(
                                    sent, local(alpha), impl=agg_impl,
                                    segment_ids=gid_l, num_segments=G))
                            if fog_compress:
                                fog_qkeys = jax.vmap(
                                    lambda i: jax.random.fold_in(fogkey, i))(
                                        jnp.arange(G))
                                fog_sent = jax.vmap(
                                    lambda k, d: comms_mod.compress_tree(
                                        fog_cc, k, d))(fog_qkeys, fog_delta)
                                agg = topo_mod.group_reduce_stacked(
                                    fog_sent, beta)
                            agg = tmap(jnp.add, base, agg)
                        else:
                            agg = tmap(jnp.add,
                                       tmap(lambda a: a[0], params_prev), agg)
                    else:
                        # direct Eq. 1 — and, for buffering hetero rounds,
                        # + Σ αᵢ·pendingᵢ, algebraically identical to the
                        # delta form (Σα = 1) but BITWISE the synchronous
                        # program when nothing is pending, which is what
                        # keeps the zero-straggler equivalence at float
                        # tolerance instead of drifting round over round
                        # (and makes the topo sync round BITWISE flat:
                        # alpha·beta telescopes to the flat weights)
                        agg = agg_mod.aggregate_stacked(params, local(w_g),
                                                        impl=agg_impl)
                        if h_buffer:
                            agg = tmap(jnp.add, agg,
                                       agg_mod.aggregate_stacked(
                                           pending, local(w_g),
                                           impl=agg_impl))
                        agg = fpsum(agg)
                    if hetero_on or fault_like:
                        # zero-accept guard: no surviving uploads → the
                        # fog node re-dispatches its previous model
                        keep = (tmap(lambda a: a[0], fog) if topo_on
                                else tmap(lambda a: a[0], params_prev))
                        agg = tmap(
                            lambda a, b: jnp.where(accept_any, a, b),
                            agg, keep)
                    if has_excl:
                        # excluded leaves have no fog-side average: the
                        # aggregated model reports GLOBAL slot 0's carried
                        # state as the representative (well-defined on any
                        # mesh; devices keep their own at re-dispatch)
                        agg = _slot0_excluded(params, agg)

                    if topo_on:
                        # ---- two-tier select: sync rounds broadcast the
                        # global model to every fog group; fog-local rounds
                        # advance each group's own model (intra-fog Eq. 1
                        # only — nothing crosses the fog→cloud tier); a
                        # group with no accepted arrivals keeps its model
                        fog_sync = tmap(
                            lambda a: jnp.broadcast_to(
                                a[None], (G,) + a.shape), agg)
                        fog_sync = tmap(
                            lambda a, b: jnp.where(accept_any, a, b),
                            fog_sync, fog)
                        if fog_local:
                            if delta_form_always:
                                fog_cand = tmap(jnp.add, fog, fog_delta)
                            else:
                                fog_cand = fpsum(agg_mod.aggregate_stacked(
                                    params, local(alpha), impl=agg_impl,
                                    segment_ids=gid_l, num_segments=G))
                                if h_buffer:
                                    fog_cand = tmap(
                                        jnp.add, fog_cand,
                                        fpsum(agg_mod.aggregate_stacked(
                                            pending, local(alpha),
                                            impl=agg_impl,
                                            segment_ids=gid_l,
                                            num_segments=G)))
                            fog_cand = tmap(
                                lambda a, b: jnp.where(
                                    group_any.reshape(
                                        (-1,) + (1,) * (a.ndim - 1)),
                                    a, b), fog_cand, fog)
                            fog = tmap(
                                lambda a, b: jnp.where(sync_f > 0, a, b),
                                fog_sync, fog_cand)
                        else:
                            fog = fog_sync
                    if h_buffer:
                        # straggler bookkeeping: transmitted backlogs clear
                        # (a DROPPED upload still clears — the device
                        # believes it delivered, so that error mass is
                        # genuinely lost), missed rounds accumulate this
                        # round's work
                        pending = _where_vec(
                            upload_l, tmap(jnp.zeros_like, backlog),
                            backlog)
                    if hetero_on:
                        # dead devices don't age: their frozen backlog is
                        # not getting staler work appended to it
                        aging = (1 if not churn_on
                                 else local(live_g).astype(jnp.int32))
                        staleness = jnp.where(upload_l > 0, 0,
                                              staleness + aging)

                    rec = {"weights": w_g, "upload_mask": mask_g,
                           "n_labeled": counts_g}
                    if topo_on:
                        # per-tier telemetry: whether this round crossed
                        # the fog→cloud link, the inter-fog Eq. 1 weights,
                        # and per-group accepted-arrival counts
                        rec["fog_sync"] = (sync_f > 0).astype(jnp.float32)
                        rec["beta"] = beta
                        rec["group_accept"] = jax.ops.segment_sum(
                            accept_g, group_ids, num_segments=G)
                    if churn_on:
                        rec["live"] = live_g
                    if faults_on:
                        rec["crashed"] = crash_live_g
                        rec["dropped"] = drop_g * upload_g
                        rec["corrupted"] = corrupt_g * recv_g
                    if guards_on:
                        rec["rejected"] = reject_g
                        rec["clipped"] = clip_g
                        rec["upload_norms"] = norms_g
                        rec["accepted"] = accept_g
                    if hetero_on:
                        rec["staleness"] = stale_g
                    if has_val:
                        rec["device_accs"] = accs_g
                        preds = jnp.argmax(eval_fn(agg, val_x), -1)
                        rec["agg_acc"] = jnp.mean(
                            (preds == val_y).astype(jnp.float32))

                    # ---- re-dispatch: fresh optimizer, pools persist.
                    # With a topology every slot reads its own GROUP's fog
                    # model (one gather per leaf; after a sync round all
                    # rows are the global model, matching the flat
                    # broadcast bitwise)
                    if topo_on:
                        dispatched = topo_mod.take_group_rows(fog, gid_l)
                    else:
                        dispatched = jax.tree_util.tree_map(
                            lambda a: jnp.broadcast_to(
                                a[None], (D_local,) + a.shape), agg)
                    params = (_keep_excluded(params, dispatched)
                              if has_excl else dispatched)
                    opt_state = trainer.opt.init(params)
                    out = (params, opt_state, pool, rng, residual, pending,
                           staleness, live)
                    if topo_on:
                        out = out + (fog,)
                    return out, rec

                carry = (state.params, state.opt_state, state.pool, state.rng,
                         state.residual, state.pending, state.staleness,
                         state.live)
                xs_rows = (keys_all, mask_arg, live_arg, fkeys)
                if topo_on:
                    # rebuild the [G, ...] fog models from the dispatched
                    # rows: one exact representative row per group (first
                    # slot), recovered shard-agnostically by a one-hot
                    # segment sum + fleet psum (rows within a group are
                    # identical by the dispatch protocol, so this also
                    # covers resuming a run that ended between syncs)
                    fidx = jax.ops.segment_min(jnp.arange(D), group_ids,
                                               num_segments=G)
                    repr_l = local(
                        jnp.zeros((D,), jnp.float32).at[fidx].set(1.0))
                    fog0 = fpsum(topo_mod.segment_sum_stacked(
                        state.params, repr_l, gid_l, G))
                    carry = carry + (fog0,)
                    xs_rows = xs_rows + (sync_flags, fog_keys)
                carry, recs = jax.lax.scan(one_round, carry, xs_rows)
                if topo_on:
                    # well-defined single returned model under any mesh:
                    # the slot-share-weighted fog mix (shares are 1.0 at
                    # G=1 → bitwise the flat row 0; after a sync round all
                    # groups are identical so the mix is exact there too)
                    gfrac = jax.ops.segment_sum(
                        jnp.ones((D,), jnp.float32), group_ids,
                        num_segments=G) / D
                    final = topo_mod.group_reduce_stacked(carry[8], gfrac)
                else:
                    final = jax.tree_util.tree_map(lambda a: a[0], carry[0])
                if has_excl:
                    # contract: the returned model's excluded leaves are
                    # GLOBAL device 0's carried state (mesh-exact via the
                    # one-hot representative, not the shard-local row 0)
                    final = _slot0_excluded(carry[0], final)
                return EngineState(*carry[:8]), recs, final

            if mesh is not None:
                dev = _fleet_spec(mesh)
                keys_spec = _fleet_spec(mesh, None)
                mask_spec = (P() if mask_mode == "bernoulli"
                             else _fleet_spec(mesh, None))
                rounds_all = jax.shard_map(
                    rounds_all, mesh=mesh,
                    # live_arg / fkeys / frates / gfactor / group_ids /
                    # sync_flags / fog_keys are replicated: liveness rows,
                    # fault draws, and the topology are global-fleet facts
                    # every shard derives identically and slices locally
                    in_specs=(dev, dev, dev, P(), P(), P(), P(),
                              keys_spec, mask_spec, P(), dev,
                              P(), P(), P(), P(), P(), P(), P()),
                    # recs and the aggregated model are replicated
                    # (all_gather / psum results), state stays sharded
                    out_specs=(dev, P(), P()), check_vma=False)

            from repro.core.federated import _donate_argnums
            return jax.jit(rounds_all, donate_argnums=_donate_argnums(0))

        key = self._cache_key("rounds_fused", False) + (
            rounds, aggregation, mask_mode, comms_key, hetero_key,
            faults_key, guards_key, churn_mode, topo_key, excl_paths)
        return _compiled(key, build)

    def run_rounds_fused(self, state: EngineState, rounds: int, *,
                         upload_mask=None, upload_fraction: float = 1.0,
                         aggregation: str = "fedavg_n", start_round: int = 0,
                         comms=None, hetero=None, faults=None, guards=None,
                         live_mask=None, topology=None, fleet=None):
        """T federated rounds (device AL + fog aggregation + re-dispatch) in
        ONE dispatch.

        Units and defaults of the knobs: ``rounds`` is a count of whole
        barrier rounds; ``upload_fraction`` (default 1.0) is a
        dimensionless per-device participation probability in (0, 1];
        ``upload_mask`` entries are truthy = uploaded; ``start_round``
        (default 0) is an absolute round index; ``aggregation`` defaults
        to ``"fedavg_n"``; ``comms`` / ``hetero`` default to None (off).

        ``aggregation`` ∈ average | weighted | optimal | fedavg_n; the
        default weights Eq. 1 by per-device labeled counts (α_i ∝ n_i, the
        correct weighting for ``federated_split``'s unbalanced shards).
        ``upload_mask`` (``[rounds, D]`` or ``[D]``, truthy = uploaded)
        models partial participation; ``upload_fraction < 1`` instead draws
        a Bernoulli mask inside the compiled program.  Weights normalize
        over actual participants; non-participants still receive the
        aggregated model (the fog node dispatches to everyone).

        Returns ``(state, recs, aggregated_params)`` where ``recs`` holds
        per-round ``weights / upload_mask / n_labeled`` (+ ``device_accs`` /
        ``agg_acc`` when the engine has a validation set) and
        ``aggregated_params`` is the last round's fog-node model.

        When chaining calls (continue training on the returned state), pass
        ``start_round`` = rounds completed so far: round 0 of any call
        consumes the state's own (evolved) keys, but the later-round key
        schedule and the Bernoulli mask keys derive from the ABSOLUTE round
        index — without the offset a second call would replay the first
        call's randomness (the same stale-seed bug class ``_select_uploads``
        had).

        ``comms`` (``core.comms.CommsConfig``) compresses each device's
        upload IN-COMPILE: the per-device delta w_i − w_dispatched (plus the
        error-feedback residual carried in ``state.residual``) goes through
        the configured codec (``int8`` stochastic quantization or ``topk``
        magnitude sparsification) before the stacked aggregation, so
        compressed rounds remain one dispatch and work unchanged under the
        shard_map mesh path.  Byte accounting stays on the host — see
        ``core.comms.comms_report`` over the returned ``recs``.  The delta
        formulation assumes ``state.params`` rows start the call identical
        (the init/re-dispatch protocol every driver follows).

        ``hetero`` (``core.hetero.HeteroConfig``) runs straggler-tolerant
        heterogeneous-fleet rounds, still in ONE dispatch: the mask becomes
        an ARRIVAL mask — either drawn in-compile as Bernoulli(1 − rate)
        when ``hetero.straggler_rate > 0``, or an explicit ``upload_mask``
        host schedule (e.g. ``hetero.straggler_schedule``) with
        ``straggler_rate == 0``; passing both is an error, not a silent
        preference.  A missing device's delta is buffered in
        ``state.pending`` and folded in on arrival weighted by
        ``alpha_i ∝ raw_i · decay(staleness_i)`` (counters in
        ``state.staleness``, also in ``recs["staleness"]``), and the
        compute profile limits per-device local fit steps via a traced step
        mask.  Composes with ``comms`` (the codec compresses the
        backlog-bearing upload; bytes are accounted only for devices that
        actually upload) and with the mesh path.  ``aggregation="optimal"``
        is argmax selection, not Eq. 1 weighting, so it does not compose
        with staleness decay and is rejected.

        ``faults`` (``core.faults.FaultConfig``) injects device churn,
        crashes, dropped uploads, wire corruption, and label-noise bursts
        IN-TRACE (all rates traced — fault sweeps reuse the executable; the
        fault key stream is its own seed, folded at absolute round
        indices).  ``guards`` (``core.faults.GuardConfig``) turns on the
        fog-side guards: non-finite and norm-outlier uploads are rejected
        (``policy="drop"``) or clipped back to the threshold
        (``policy="clip"``), counted in ``recs["rejected"]`` /
        ``recs["clipped"]``, and Eq. 1 renormalizes over the accepted
        arrivals; an all-rejected round keeps the previous fog model.
        ``live_mask`` (``[rounds, D]`` or ``[D]``, truthy = live) drives
        churn from a host schedule (``core.faults.liveness_schedule``)
        instead of the in-trace birth/death process — passing it alongside
        ``faults.death_rate``/``birth_rate`` > 0 is an error.  Liveness is
        carried in ``state.live``; dead slots are bitwise inert and rejoin
        with the current fog model at the next dispatch.  All of it
        composes with ``comms``, ``hetero``, and the mesh, and the round
        stays ONE dispatch.

        ``topology`` (``core.topology.FogTopology``) runs the rounds as a
        two-tier edge×fog hierarchy: every round each fog group aggregates
        its OWN slots (intra-fog Eq. 1 — per-group masked normalization,
        a group with no accepted arrivals keeps its model), and only every
        ``local_steps``-th round the G fog models aggregate to a global
        one (inter-fog Eq. 1, β ∝ group arrival mass) and cross the
        fog→cloud link — per-tier byte accounting in
        ``core.comms.tier_report``.  ``uniform_topology(D, 1)`` reduces
        bitwise to the flat program; composes with ``comms`` (plus an
        optional second ``comms.fog_compression`` codec on the fog→cloud
        deltas), ``hetero``, ``faults``/``guards`` (guard medians go
        per-group), and both the 1-D and the 2-D ``("fog", "device")``
        mesh (``launch.mesh.make_fog_mesh``), still in ONE dispatch.
        ``aggregation="optimal"`` selects one argmax model, which has no
        two-level decomposition, and is rejected.

        ``fleet`` (``core.fleet.FleetConfig``) bundles
        ``comms``/``hetero``/``faults``/``guards``/``live_mask``/
        ``topology`` as one value; the per-feature kwargs keep working
        and may not be mixed with ``fleet=`` without a warning (legacy
        values win).  ``async_cfg``/``stream`` fields are rejected here —
        they belong to the async event loop (``run_async``).
        """
        from repro.core import fleet as fleet_mod
        fleet = fleet_mod.resolve_fleet(
            fleet, "run_rounds_fused",
            allowed=("comms", "hetero", "faults", "guards", "live_mask",
                     "topology"),
            comms=comms, hetero=hetero, faults=faults, guards=guards,
            live_mask=live_mask, topology=topology)
        comms, hetero, faults = fleet.comms, fleet.hetero, fleet.faults
        guards, live_mask = fleet.guards, fleet.live_mask
        topology = fleet.topology
        if aggregation not in _AGGREGATIONS:
            raise ValueError(f"unknown aggregation {aggregation!r}: "
                             f"use {' | '.join(_AGGREGATIONS)}")
        if aggregation in ("weighted", "optimal") and self.test_images is None:
            raise ValueError(
                f"aggregation={aggregation!r} scores devices on a validation "
                "set; construct EdgeEngine with test_set")
        if hetero is not None and aggregation == "optimal":
            raise ValueError(
                "aggregation='optimal' picks one argmax model and has no "
                "Eq. 1 weights for staleness decay to act on; use "
                "average | weighted | fedavg_n with hetero")
        if guards is not None and guards.policy == "off":
            guards = None
        if aggregation == "optimal" and (
                faults is not None or guards is not None
                or live_mask is not None):
            raise ValueError(
                "aggregation='optimal' picks one argmax model, not Eq. 1 "
                "weights, so liveness masking and guard rejection have "
                "nothing to renormalize; use average | weighted | fedavg_n "
                "with faults/guards/live_mask")
        if live_mask is not None and faults is not None and faults.has_churn:
            raise ValueError(
                "pass either an explicit live_mask host schedule or "
                "faults.death_rate/birth_rate for the in-trace churn "
                "process, not both (set the rates to 0 to drive churn "
                "from the schedule)")
        if topology is not None:
            topology.validate_for(self.num_devices)
            if aggregation == "optimal":
                raise ValueError(
                    "aggregation='optimal' picks one argmax model — there "
                    "is no two-level Eq. 1 decomposition to run per fog "
                    "group; use average | weighted | fedavg_n with a "
                    "topology")
        self._check_capacity(state, rounds=rounds)
        D = self.num_devices
        comms_key = None
        wire = ("float32" if comms is None
                else getattr(comms, "compute_dtype", "float32"))
        if comms is not None and (comms.compression != "none"
                                  or wire != "float32"):
            # a sub-f32 wire is a lossy codec in its own right: it forces
            # the delta-form program (and may carry an EF residual) even
            # at compression="none"
            comms_key = (comms.compression, comms.topk_fraction,
                         comms.error_feedback, wire)
            if comms.error_feedback and not jax.tree_util.tree_leaves(
                    state.residual):
                # fresh error-feedback buffer, mirroring params (inherits
                # the device-axis sharding from the stacked params)
                state = state._replace(residual=jax.tree_util.tree_map(
                    jnp.zeros_like, state.params))
        if comms_key is None or not comms_key[2]:
            # codec off (or EF off): drop any stale residual so the compiled
            # carry structure matches and old buffers can't leak in
            state = state._replace(residual=())
        hetero_key = None
        step_limits = None
        if hetero is not None:
            step_limits = hetero_mod.device_step_limits(
                hetero, D, self.cfg.train_steps_per_acq)
            hetero_key = (hetero.decay, float(hetero.decay_rate),
                          bool(hetero.buffer_stale), step_limits is not None)
            if hetero.straggler_rate > 0.0:
                if upload_mask is not None or upload_fraction < 1.0:
                    # refusing to guess which participation model wins:
                    # silently preferring one would run e.g. a 30%
                    # straggler config as a 10% one with telemetry
                    # (expected_staleness, bench ratios) reporting the
                    # other
                    raise ValueError(
                        "pass either hetero.straggler_rate or an explicit "
                        "upload_mask/upload_fraction participation model, "
                        "not both (set straggler_rate=0 to drive hetero "
                        "rounds from a host schedule)")
                # the straggler model IS the participation machinery: draw
                # the arrival mask in-compile at Bernoulli(1 − rate)
                upload_fraction = 1.0 - hetero.straggler_rate
            if not jax.tree_util.tree_leaves(state.staleness):
                state = state._replace(
                    staleness=jnp.zeros((D,), jnp.int32))
            if hetero.buffer_stale:
                if not jax.tree_util.tree_leaves(state.pending):
                    state = state._replace(pending=jax.tree_util.tree_map(
                        jnp.zeros_like, state.params))
            else:
                state = state._replace(pending=())
            state = self._shard_state(state)
        else:
            # hetero off: drop any carried buffers so the compiled carry
            # structure matches (mirrors the residual hygiene above)
            state = state._replace(pending=(), staleness=())
        topo_key = None
        if topology is not None:
            # the per-group compute profile composes with (caps) any
            # hetero step budgets; fog codec choice is static, the rest
            # of the topology (group ids, cadence flags) rides as traced
            # arguments so regrouping at equal G reuses the executable
            step_limits = topo_mod.topology_step_limits(
                topology, D, self.cfg.train_steps_per_acq,
                base=step_limits)
            fog_comp = (getattr(comms, "fog_compression", "none")
                        if comms is not None else "none")
            topo_key = (topology.num_groups, int(topology.local_steps),
                        fog_comp, topology.compute_scale is not None)
        # churn/fault statics.  churn_mode is "process" whenever faults are
        # on (zero birth/death rates leave the fleet fully live), so
        # fault-rate sweeps share one executable.
        churn_mode = ("given" if live_mask is not None
                      else "process" if faults is not None else "none")
        if churn_mode != "none":
            if not jax.tree_util.tree_leaves(state.live):
                state = state._replace(live=jnp.ones((D,), jnp.float32))
            state = self._shard_state(state)
        else:
            # churn off: drop any carried liveness (same hygiene as the
            # residual/pending/staleness buffers above)
            state = state._replace(live=())
        faults_key = faults_mod.faults_static_key(faults,
                                                  self._num_classes())
        guards_key = faults_mod.guards_static_key(guards)
        # round 0 consumes the incoming state's keys; later rounds follow
        # the legacy set_params schedule (device_keys at the absolute index)
        later = [self.device_keys(start_round + t) for t in range(1, rounds)]
        keys_all = (jnp.stack([state.rng] + later) if later
                    else state.rng[None])
        fraction = jnp.float32(1.0)
        if upload_mask is not None:
            m = np.asarray(upload_mask, np.float32)
            if m.ndim == 1:
                m = np.broadcast_to(m, (rounds, D))
            if m.shape != (rounds, D):
                raise ValueError(f"upload_mask shape {m.shape} != "
                                 f"{(rounds, D)}")
            mask_mode, mask_arg = "given", jnp.asarray(m)
        elif upload_fraction < 1.0:
            mask_mode = "bernoulli"
            base = jax.random.key(self.cfg.seed + 0x6D61)
            mask_arg = jax.vmap(lambda t: jax.random.fold_in(base, t))(
                jnp.arange(start_round, start_round + rounds))
            fraction = jnp.float32(upload_fraction)
        else:
            mask_mode = "given"
            mask_arg = jnp.ones((rounds, D), jnp.float32)
        if live_mask is not None:
            lm = np.asarray(live_mask, np.float32)
            if lm.ndim == 1:
                lm = np.broadcast_to(lm, (rounds, D))
            if lm.shape != (rounds, D):
                raise ValueError(f"live_mask shape {lm.shape} != "
                                 f"{(rounds, D)}")
            live_arg = jnp.asarray(lm)
        else:
            live_arg = jnp.ones((rounds, D), jnp.float32)
        # the fault surface is traced: per-round fault keys (absolute
        # indices), the rates vector, and the guard factor all ride along
        # as arguments, with inert fill-ins when the features are off
        fkeys = (faults_mod.fault_keys(faults, start_round, rounds)
                 if faults is not None
                 else jax.random.split(jax.random.key(0), rounds))
        frates = jnp.asarray(faults_mod.rates_vector(faults))
        gfactor = jnp.float32(guards.norm_factor if guards is not None
                              else 0.0)
        fn = self._get_rounds_fused_jit(rounds, aggregation, mask_mode,
                                        comms_key, hetero_key, faults_key,
                                        guards_key, churn_mode, topo_key,
                                        self._exclude_paths(state.params))
        # the compute profile is a traced [D] argument (profile sweeps reuse
        # the executable); a full-budget fill-in rides along when unused
        sl = jnp.asarray(
            step_limits if step_limits is not None
            else np.full((D,), self.cfg.train_steps_per_acq, np.int32))
        # topology rides as traced arguments: the [D] group-id vector, the
        # [rounds] fog→cloud sync flags (absolute-indexed, so chained calls
        # keep the cadence), and per-round fog-codec keys (own stream,
        # folded at absolute round indices); inert fill-ins when off
        if topology is not None:
            group_ids = jnp.asarray(topology.ids)
            sync_rows = jnp.asarray(
                topo_mod.sync_schedule(topology, rounds, start_round))
            fbase = jax.random.key(self.cfg.seed + 0x666F67)
            fog_keys = jax.vmap(lambda t: jax.random.fold_in(fbase, t))(
                jnp.arange(start_round, start_round + rounds))
        else:
            group_ids = jnp.zeros((D,), jnp.int32)
            sync_rows = jnp.ones((rounds,), jnp.float32)
            fog_keys = jax.random.split(jax.random.key(0), rounds)
        counters.count_dispatch()
        state, recs, final = fn(state, self.images, self.labels,
                                self.seed_images, self.seed_labels,
                                self.test_images, self.test_labels,
                                keys_all, mask_arg, fraction, sl,
                                live_arg, fkeys, frates, gfactor,
                                group_ids, sync_rows, fog_keys)
        return state, recs, final

    # -------------------------------------------------- async event loop
    def run_async(self, state: EngineState, events: int, *, async_cfg=None,
                  aggregation: str = "fedavg_n", comms=None,
                  start_event: int = 0, faults=None, guards=None,
                  topology=None, stream=None, hetero=None, fleet=None):
        """Rounds-free FedAsync/FedBuff aggregation: ``events`` quorum- or
        timer-triggered fog aggregation events over a continuous-time
        device latency model, in ONE dispatch — see
        ``core.async_engine.run_events_fused`` (this is a thin delegate so
        the engine's three execution modes live on one object: ``run_round``
        / ``run_rounds_fused`` / ``run_async``).  ``faults`` / ``guards``
        are the ``core.faults`` fault-injection and aggregation-guard
        configs; async churn always uses the in-trace birth/death process
        (there is no host liveness schedule for event time).  ``stream``
        (``core.stream.StreamConfig``) adds live traffic + the
        serve/escalate cascade; ``fleet`` (``core.fleet.FleetConfig``)
        bundles all the knobs as one value."""
        from repro.core.async_engine import run_events_fused
        return run_events_fused(self, state, events, async_cfg=async_cfg,
                                aggregation=aggregation, comms=comms,
                                start_event=start_event, faults=faults,
                                guards=guards, topology=topology,
                                stream=stream, hetero=hetero, fleet=fleet)

    # ------------------------------------------------------------ drivers
    def run_round(self, state: EngineState, *, record_curves: bool = True):
        """The tentpole: R acquisitions × D devices in ONE dispatch."""
        record = record_curves and self.test_images is not None
        self._check_capacity(state)
        fn = self._get_round_jit(record)
        counters.count_dispatch()
        state, recs = fn(state, self.images, self.labels,
                         *self._data_args(record))
        return state, recs

    def run_round_legacy(self, state: EngineState, *,
                         record_curves: bool = True):
        """Flagged legacy path: same step function, dispatched per device per
        acquisition from Python (D×R dispatches). Numerically equivalent to
        ``run_round`` — kept for equivalence tests and as the bench baseline.
        """
        record = record_curves and self.test_images is not None
        self._check_capacity(state)
        fn = self._get_step_jit(record)
        data_args = self._data_args(record)
        R = self.cfg.acquisitions
        out_carries, out_recs = [], []
        for d in range(self.num_devices):
            carry = jax.tree_util.tree_map(
                lambda a: a[d], (state.params, state.opt_state, state.pool,
                                 state.rng))
            img_d, lbl_d = self.images[d], self.labels[d]
            recs = []
            for _ in range(R):
                counters.count_dispatch()
                carry, rec = fn(carry, img_d, lbl_d, *data_args)
                recs.append(rec)
            out_carries.append(carry)
            out_recs.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *recs))
        carry = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *out_carries)
        recs = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *out_recs)
        return EngineState(*carry), recs

    # ------------------------------------------------------------ reporting
    def histories(self, recs) -> List[List[dict]]:
        """Convert stacked records [D, R, ...] into legacy history dicts."""
        n_lab = np.asarray(recs["n_labeled"])
        sel = np.asarray(recs["selected"])
        acc = np.asarray(recs["test_acc"]) if "test_acc" in recs else None
        out = []
        for d in range(n_lab.shape[0]):
            hist = []
            for r in range(n_lab.shape[1]):
                rec = {"device": d, "acquisition": r + 1,
                       "n_labeled": int(n_lab[d, r]),
                       "selected": sel[d, r][sel[d, r] >= 0].tolist()}
                if acc is not None:
                    rec["test_acc"] = float(acc[d, r])
                hist.append(rec)
            out.append(hist)
        return out
