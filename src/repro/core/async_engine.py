"""Rounds-free async aggregation: a continuous-time fog-node event loop.

Every engine so far — even the straggler-tolerant hetero rounds — is
ROUND-synchronous: the fog node aggregates at a global barrier, and a
device either makes the barrier or banks its delta for the next one.  Real
fog deployments (Hussain, *Federated Fog Computing for Remote Industry 4.0
Applications*; Kumar & Srirama, *Fog enabled distributed training
architecture for federated learning*) do not run barriers: devices finish
whenever they finish, and the fog node aggregates on a TIMER or when a
QUORUM of uploads has buffered — the FedAsync (Xie et al.) / FedBuff
(Nguyen et al.) protocol family.

This module makes that a first-class engine, still honoring the repo's
compile-once / one-dispatch discipline:

* **Continuous-time device model.** Each device draws a completion latency
  for every local round it is dispatched (``AsyncConfig.dist``:
  exponential, lognormal, or deterministic, around a per-device mean from
  ``device_latency_means`` — a log-spaced slow/fast skew profile or
  explicit means).  Latency is SIMULATED seconds: the virtual clock it
  advances is telemetry, not host wall time.

* **Quorum-of-K or timer.** The fog node aggregates at
  ``t_event = min(K-th smallest completion time, t_last + timer)`` —
  whichever fires first.  ``quorum=1`` is FedAsync (immediate
  staleness-decayed mixing per completion), ``quorum=K`` is FedBuff
  (K-buffered aggregation), ``timer=τ`` alone is a pure wall-clock
  aggregation cadence.  Both knobs are TRACED (the quorum is a sorted-array
  index, the timer a scalar), so sweeping K or τ reuses the compiled
  executable.

* **One dispatch.** The event loop lowers to a ``lax.scan`` over
  aggregation events.  The priority queue is encoded as a per-device
  next-completion-time array ``[D]``: the "pop" is a ``jnp.sort`` /
  ``jnp.argmin`` over that array inside the trace — no host round-trip
  ever sequences events.  Per event, the candidate local round runs for
  the WHOLE fleet (static shapes) and commits only for devices that were
  actually dispatched, exactly the masking discipline the hetero engine
  uses.

* **Composition.** Uploads are aggregated in delta form
  ``W ← W + η·Σ αᵢ·C(Δᵢ)`` with ``αᵢ ∝ rawᵢ·decay(staleness_i)``
  (``aggregation.staleness_weights`` — the same staleness machinery as
  ``core.hetero``), so the comms codecs (``core.comms``) compress each
  uploaded delta unchanged, ``EngineState.pending`` carries the in-flight
  delta and ``EngineState.staleness`` the model-version age, and the
  shard_map mesh path works unchanged (completion times and staleness are
  two more all_gather'd ``[D]`` scalars; pending stays device-local).

* **Exact reduction.** With ``mean_latency=0`` and ``quorum=D`` every
  device completes instantly and every event is a full barrier: the event
  loop IS ``EdgeEngine.run_rounds_fused`` (same key schedule, same Eq. 1
  weights) to float tolerance (≤ 1e-5, delta-form summation order only),
  under vmap and under the mesh — pinned by ``tests/test_async_engine.py``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import aggregation as agg_mod
from repro.core import cascade as cascade_mod
from repro.core import comms as comms_mod
from repro.core import counters, vpool
from repro.core import faults as faults_mod
from repro.core import fleet as fleet_mod
from repro.core import hetero as hetero_mod
from repro.core import stream as stream_mod
from repro.core.hetero import DECAYS

DISTS = ("exp", "lognormal", "det")

_ASYNC_AGGREGATIONS = ("average", "weighted", "fedavg_n")


@dataclass(frozen=True)
class AsyncConfig:
    """Static policy for the rounds-free async event loop.

    Trigger (at least one of ``quorum`` / ``timer`` must be set):

    ``quorum``
        int ≥ 1 or None (default ``None``).  Aggregate as soon as this many
        devices have completed since their dispatch — the K-th smallest
        entry of the completion-time array.  Values above the fleet size
        clamp to D (a full barrier).  ``1`` = FedAsync, ``K`` = FedBuff.
    ``timer``
        float > 0, SIMULATED seconds, or None (default ``None``).
        Aggregate at most this long after the previous event, even if the
        quorum has not filled (possibly aggregating nothing — the fog
        model is then re-dispatched unchanged).

    Latency model (all times in simulated seconds):

    ``dist``
        ``"exp" | "lognormal" | "det"`` (default ``"exp"``).  Shape of the
        per-round completion-latency draw around each device's mean.
        ``det`` draws the mean exactly — ``mean_latency=0`` with ``det``
        (or any dist; the mean scales the draw) is the synchronous limit.
    ``mean_latency``
        float ≥ 0, simulated seconds (default ``1.0``).  Fleet-wide
        geometric-mean completion latency.
    ``latency_skew``
        float ≥ 1, dimensionless (default ``1.0``).  Ratio of the slowest
        device's mean latency to the fastest; per-device means are
        log-spaced over ``[mean/√skew, mean·√skew]`` (device 0 fastest).
    ``device_means``
        optional explicit per-device mean latencies, simulated seconds
        (tuple of length D; overrides ``mean_latency``/``latency_skew``).
    ``sigma``
        float > 0, dimensionless (default ``0.5``).  Lognormal shape
        parameter; the draw is mean-preserving
        (``mean·exp(σZ − σ²/2)``).  Ignored for other dists.

    Aggregation:

    ``decay`` / ``decay_rate``
        Staleness discount for Eq. 1 weights, measured in MODEL VERSIONS
        (committed aggregation events) between a device's dispatch and its
        arrival: ``exp`` → ``rate**s`` (rate ∈ (0, 1], default kind) …
        ``poly`` → ``(1+s)**-rate`` (Xie et al.) … ``none`` → 1.
        Defaults ``"poly"`` / ``0.5`` — the FedAsync paper's choice; the
        hetero engine defaults to ``exp`` because its staleness unit is
        whole rounds.
    ``mix_rate``
        float in (0, 1], dimensionless (default ``1.0``).  Server mixing
        rate η: ``W ← W + η·Σ αᵢ·Δᵢ``.  Must be 1.0 to reduce exactly to
        the synchronous round.
    ``seed``
        int (default ``0``).  Seeds the latency draws (independent of the
        experiment seed, so the same fleet timing can be replayed across
        AL configs).
    """

    quorum: Optional[int] = None
    timer: Optional[float] = None
    dist: str = "exp"
    mean_latency: float = 1.0
    latency_skew: float = 1.0
    device_means: Optional[Tuple[float, ...]] = None
    sigma: float = 0.5
    decay: str = "poly"
    decay_rate: float = 0.5
    mix_rate: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.quorum is None and self.timer is None:
            raise ValueError(
                "AsyncConfig needs a trigger: set quorum (K completions), "
                "timer (simulated seconds), or both")
        if self.quorum is not None and self.quorum < 1:
            raise ValueError(f"quorum must be >= 1, got {self.quorum}")
        if self.timer is not None and self.timer <= 0.0:
            raise ValueError(f"timer must be > 0 simulated seconds, "
                             f"got {self.timer}")
        if self.dist not in DISTS:
            raise ValueError(f"unknown latency dist {self.dist!r}: "
                             f"use {' | '.join(DISTS)}")
        if self.mean_latency < 0.0:
            raise ValueError(
                f"mean_latency must be >= 0, got {self.mean_latency}")
        if self.latency_skew < 1.0:
            raise ValueError(
                f"latency_skew is slowest/fastest >= 1, "
                f"got {self.latency_skew}")
        if self.sigma <= 0.0:
            raise ValueError(f"sigma must be > 0, got {self.sigma}")
        if self.decay not in DECAYS:
            raise ValueError(f"unknown decay {self.decay!r}: "
                             f"use {' | '.join(DECAYS)}")
        if self.decay_rate <= 0.0:
            raise ValueError(f"decay_rate must be > 0, got {self.decay_rate}")
        if self.decay == "exp" and self.decay_rate > 1.0:
            raise ValueError(
                f"exp decay_rate is the per-version factor gamma in (0, 1], "
                f"got {self.decay_rate}")
        if not 0.0 < self.mix_rate <= 1.0:
            raise ValueError(f"mix_rate must be in (0, 1], "
                             f"got {self.mix_rate}")


def device_latency_means(cfg: AsyncConfig, num_devices: int) -> np.ndarray:
    """Per-device mean completion latency ``[D] float32``, simulated seconds.

    Explicit ``cfg.device_means`` win (shape-checked); otherwise means are
    log-spaced over ``[mean/√skew, mean·√skew]`` so slowest/fastest =
    ``latency_skew`` and the geometric mean is ``mean_latency`` (device 0
    fastest — deterministic, so sweeps and tests can reason about order
    statistics).  Host-side numpy; the result enters the compiled event
    loop as a traced ``[D]`` argument, so changing the latency profile
    does NOT recompile.
    """
    if cfg.device_means is not None:
        means = np.asarray(cfg.device_means, np.float32)
        if means.shape != (num_devices,):
            raise ValueError(f"device_means shape {means.shape} != "
                             f"({num_devices},)")
        if (means < 0).any():
            raise ValueError("device_means must be >= 0 simulated seconds")
        return means
    if cfg.latency_skew == 1.0 or num_devices == 1:
        return np.full((num_devices,), cfg.mean_latency, np.float32)
    half = np.sqrt(cfg.latency_skew)
    return (cfg.mean_latency
            * np.geomspace(1.0 / half, half, num_devices)).astype(np.float32)


def _draw_latency(cfg_key, key, means):
    """One completion-latency draw per device ``[D]``, simulated seconds.

    ``cfg_key`` is the static ``(dist, sigma)`` pair.  All draws scale the
    per-device mean, so ``mean == 0`` is exactly zero latency under every
    dist (the synchronous limit the equivalence contract relies on).
    """
    dist, sigma = cfg_key
    if dist == "det":
        return means
    if dist == "exp":
        return means * jax.random.exponential(key, means.shape)
    z = jax.random.normal(key, means.shape)
    return means * jnp.exp(sigma * z - 0.5 * sigma * sigma)


def _where_mask(mask, on_true, on_false):
    """Leafwise ``jnp.where`` with a ``[D]`` mask broadcast to each leaf's
    leading device axis."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(
            mask.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, b),
        on_true, on_false)


def _get_async_jit(engine, events: int, aggregation: str, comms_key,
                   async_key, faults_key=None, guards_key=None,
                   churn_mode: str = "none", topo_key=None,
                   stream_key=None, hetero_steps: bool = False,
                   excl_paths: tuple = ()):
    """The whole event loop — every aggregation event, every candidate
    device round, every staleness-decayed delta fold-in — as ONE compiled
    program (a ``lax.scan`` over aggregation events).

    ``async_key`` is the STATIC part of the ``AsyncConfig``:
    ``(dist, sigma, has_quorum, has_timer, decay, decay_rate)``.  The
    quorum size, timer period, mix rate, and per-device latency means all
    arrive as TRACED arguments — sweeping any of them (the bench does)
    reuses the executable.

    Per scan step (one aggregation event):

    1. devices flagged for dispatch at the previous event take the fog
       model, run their local AL round (the candidate round runs for the
       whole fleet; commits are masked), bank their delta in ``pending``,
       and draw a completion latency → ``next_done = t_now + L``;
    2. the event time is ``min(K-th smallest next_done, t_now + timer)``
       (the argmin/sort "pop" of the encoded priority queue);
    3. devices with ``next_done ≤ t_event`` ARRIVE: their pending deltas
       (compressed by the comms codec if configured) fold into the fog
       model with ``αᵢ ∝ rawᵢ·decay(stalenessᵢ)`` weights; a zero-arrival
       timer event re-dispatches the fog model unchanged (and, because no
       model version was committed, ages nobody);
    4. arrivals reset staleness and are flagged for re-dispatch; everyone
       still in flight ages by one model version iff a commit happened.

    ``topo_key`` (``(num_groups, local_steps, has_compute_profile)`` or
    None) threads the fog tier (``core.topology``) through the event loop:
    the fog model carry becomes a ``[G, ...]`` stack, each arrival folds
    into ITS OWN fog group's model (intra-fog Eq. 1 with per-group
    staleness weights), and every ``local_steps``-th event is a SYNC event
    that collapses the tier — the β-mixed inter-fog base plus the flat
    staleness-decayed arrivals, broadcast back to every group.  ``G=1``
    with ``local_steps=1`` makes every event a sync event with β ≡ 1.0,
    reproducing the flat loop bitwise.  The guard verdict is per-group
    (one fog's byzantine burst cannot skew another's threshold) and
    staleness ages against the model the device actually dispatched from —
    its group's on local events, the global on sync events.  With a
    compute profile the per-group step budgets ride as a traced ``[D]``
    ``step_limits`` argument masking local fit steps (the same surface as
    the sync engine's hetero profile): a slow fog group trains LESS per
    dispatch and arrives late.

    ``stream_key`` (``(process, queue_cap, max_arrivals, escalate_k,
    selection)`` or None) turns on live traffic (``core.stream``): per
    event, each device receives a Poisson/bursty batch of unlabeled
    requests over the event's simulated-seconds gap (sampled under the
    optional drifting label tilt) into a bounded queue carried per device;
    devices that COMMITTED a local round this event score their queue with
    the acquisition scorer and ``cascade.cascade_decide`` serves confident
    requests locally (graded against ground truth for telemetry), escalates
    the top-``escalate_k`` informative ones into the training pool (the
    fog labels them — active learning on traffic), and leaves the rest
    queued until backpressure drops them.  All rates/thresholds/drift
    knobs are traced; the stream draws live on a DEDICATED key stream and
    the pool advances only for devices that actually escalated, so a
    zero-rate stream replays the plain event loop bit-for-bit.

    ``faults_key`` / ``guards_key`` / ``churn_mode`` mirror the
    ``core.faults`` statics of ``EdgeEngine._get_rounds_fused_jit``.
    Event-time semantics: churn (always the in-trace birth/death process —
    there is no host schedule for event time) is stepped at each event's
    start: a device that dies parks its queue slot at ``+inf`` (it can
    never arrive — the arrival test requires a FINITE completion time), a
    slot that rebirths is freshly dispatched the current fog model with
    zero staleness.  A crash loses the local round's work (the commit is
    reverted, so the banked delta is the zero it started with) AND spikes
    the completion latency by ``restart_mult`` — the device restarts and
    reports late, delivering nothing useful.  Drops, wire corruption, and
    the guard verdict act on the ARRIVED uploads exactly as in the sync
    engine, with the fog commit gated on accepted (not merely arrived)
    uploads.

    ``hetero_steps`` is True when a ``HeteroConfig`` compute profile
    contributes to the traced ``step_limits`` vector (min-composed with
    any topology ``compute_scale`` budgets on the host) — the static that
    turns the per-device step masking on without a topology.

    ``excl_paths`` is the adapter's static tuple of flat leaf paths
    excluded from Eq. 1 (``model_adapter.excluded_paths``): excluded
    leaves — per-device recurrent/SSM state — never enter the banked
    deltas, survive every dispatch with the device's OWN value, and the
    fog model carries the GLOBAL slot-0 copy as representative (one-hot
    + fleet psum, mesh-exact).  Empty tuple emits the unchanged program.
    """
    from repro.core import topology as topo_mod
    from repro.core.engine import (_compiled, _fleet_collectives,
                                   _fleet_spec, fleet_shards)
    from repro.core.federated import _donate_argnums

    def build():
        from jax.sharding import PartitionSpec as P

        # non-None comms_key == lossy wire: a real codec OR a sub-f32
        # compute_dtype (the bf16 wire rounds values in-compile too)
        compress = comms_key is not None
        use_ef = compress and comms_key[2]
        cc = (comms_mod.CommsConfig(compression=comms_key[0],
                                    topk_fraction=comms_key[1],
                                    error_feedback=comms_key[2],
                                    compute_dtype=comms_key[3])
              if compress else None)
        agg_impl = engine.aggregate_impl
        dist, sigma, has_quorum, has_timer, decay, decay_rate = async_key
        dist_key = (dist, sigma)
        faults_on = faults_key is not None
        guards_on = guards_key is not None
        churn_on = churn_mode != "none"
        fault_like = faults_on or guards_on or churn_on
        if faults_on:
            corrupt_mode, num_classes = faults_key
        topo_on = topo_key is not None
        G = topo_key[0] if topo_on else 1
        use_steps = (topo_on and topo_key[2]) or hetero_steps
        stream_on = stream_key is not None
        if stream_on:
            s_process, Q, A_max, esc_k, s_selection = stream_key
        acq_random = engine.cfg.acquisition_fn == "random"
        ncls = engine._num_classes()
        T_mc = engine.cfg.mc_samples
        score_fn = engine._score_fn
        step = engine._acquisition_step(False)
        R = engine.cfg.acquisitions
        round_unroll = R if engine.unroll else 1
        has_val = engine.test_images is not None
        mesh = engine.mesh
        on_mesh = mesh is not None
        D = engine.num_devices
        D_local = D // fleet_shards(mesh)
        trainer = engine.trainer
        eval_fn = trainer.eval_logits_raw
        tmap = jax.tree_util.tree_map
        gather, local, fpsum = _fleet_collectives(mesh, D)
        # adapter-excluded leaves (per-device recurrent state, out of
        # Eq. 1) — gated on has_excl so the empty tuple emits the
        # unchanged pre-adapter program (same contract as the sync engine)
        has_excl = bool(excl_paths)
        excl_set = frozenset(excl_paths)
        twp = jax.tree_util.tree_map_with_path

        def _is_excl(kp):
            return agg_mod._path_str(kp) in excl_set

        def _zero_excluded(tree):
            # excluded leaves carry no Eq. 1 mass: zeroed out of the
            # banked deltas so EF residuals, guard norms, and the fog
            # fold-ins see only aggregated state
            return twp(lambda kp, a: (jnp.zeros_like(a) if _is_excl(kp)
                                      else a), tree)

        def _keep_excluded(own, incoming):
            # dispatch select: excluded leaves keep each device's OWN
            # value, the rest take the incoming fog model
            return twp(lambda kp, t, d: t if _is_excl(kp) else d,
                       own, incoming)

        def events_all(state, images, labels, valid, seed_x, seed_y,
                       val_x, val_y, keys_all, lat_keys, skeys, means_g,
                       quorum, timer, mix_rate, step_limits, srates, svec,
                       fkeys, frates, gfactor, group_ids, sync_flags):
            n_pad = labels.shape[1]
            if topo_on:
                gid_l = local(group_ids)
                # global-eval mix: each fog's slot share of the fleet (a
                # size-weighted model average is the cloud-side estimate
                # between sync events; 1.0 at G=1 → bitwise the flat fog)
                gfrac = jax.ops.segment_sum(
                    jnp.ones((D,), jnp.float32), group_ids,
                    num_segments=G) / D

            def one_event(carry, xs):
                (fog, params, opt_state, pool, rng, residual, pending,
                 staleness, next_done, dispatch, t_now, live) = carry[:12]
                if stream_on:
                    q_idx, q_valid = carry[12], carry[13]
                keys_r, lat_key, fkey, *xtra = xs
                if topo_on:
                    sync_f, *xtra = xtra
                if stream_on:
                    skey, = xtra

                # ---- 0. churn + fault draws for this event (one fault key
                # per event, folded at the absolute index)
                if faults_on or churn_on:
                    k_live, k_flt, k_labels = jax.random.split(fkey, 3)
                live_g = None
                if churn_on:
                    live_prev = live
                    live_g = faults_mod.update_liveness(
                        k_live, gather(live),
                        frates[faults_mod.RATE_DEATH],
                        frates[faults_mod.RATE_BIRTH])
                    live = local(live_g)
                    born = (live > 0) & (live_prev <= 0)
                    # a dead device leaves the queue (its slot parks at
                    # +inf — it can never arrive) and cancels any pending
                    # dispatch; a reborn slot is freshly dispatched the
                    # current fog model with zero staleness
                    dispatch = jnp.where(live > 0,
                                         jnp.where(born, 1.0, dispatch),
                                         0.0)
                    next_done = jnp.where(live > 0, next_done,
                                          jnp.float32(jnp.inf))
                    staleness = jnp.where(born, 0, staleness)
                if faults_on:
                    crash_g, drop_g, corrupt_g, noise_g = \
                        faults_mod.draw_fault_masks(k_flt, frates, D)
                    if live_g is not None:
                        crash_g = crash_g * live_g
                    crash_l = local(crash_g)

                # label-noise burst: flagged devices train this event on
                # uniformly random labels (global draw, sliced local)
                labels_r = labels
                if faults_on:
                    noisy_l = local(jax.random.randint(
                        k_labels, (D, n_pad), 0, num_classes,
                        dtype=labels.dtype))
                    noise_l = local(noise_g)
                    labels_r = jnp.where(noise_l[:, None] > 0,
                                         noisy_l, labels)

                # ---- 1. dispatch + candidate round (masked commit):
                # every slot reads ITS fog group's model (flat = the one
                # implicit group, a plain broadcast)
                if topo_on:
                    fog_b = topo_mod.take_group_rows(fog, gid_l)
                else:
                    fog_b = tmap(lambda a: jnp.broadcast_to(
                        a[None], (D_local,) + a.shape), fog)
                if has_excl:
                    # dispatch never overwrites per-device excluded state
                    fog_b = _keep_excluded(params, fog_b)
                params = _where_mask(dispatch, fog_b, params)
                opt_state = _where_mask(dispatch, trainer.opt.init(params),
                                        opt_state)
                params_base = params

                def device_round(c, images_d, labels_d, steps_d):
                    # steps_d: the fog compute profile — a slow group's
                    # slots mask out local fit steps past their budget
                    # (the sync engine's hetero surface), so they train
                    # LESS per dispatch and arrive late
                    return jax.lax.scan(
                        lambda cc_, _: step(cc_, images_d, labels_d,
                                            seed_x, seed_y, None, None,
                                            steps_d if use_steps else None),
                        c, None, length=R, unroll=round_unroll)

                (p2, o2, pool2, rng2), _ = jax.vmap(device_round)(
                    (params, opt_state, pool, keys_r), images, labels_r,
                    step_limits)
                # a crashed device loses the round: nothing commits, so the
                # delta it banks is the zero its fresh dispatch started
                # with — it restarts and reports late (latency spike below)
                # with nothing useful to deliver
                commit = (dispatch * (1.0 - crash_l) if faults_on
                          else dispatch)
                params = _where_mask(commit, p2, params)
                opt_state = _where_mask(commit, o2, opt_state)
                pool = _where_mask(commit, pool2, pool)
                rng = jnp.where(commit > 0, rng2, rng)
                banked = tmap(jnp.subtract, params, params_base)
                if has_excl:
                    banked = _zero_excluded(banked)
                pending = _where_mask(commit, banked, pending)
                # same key on every shard → consistent global latency draw
                lat_g = _draw_latency(dist_key, lat_key, means_g)
                if faults_on:
                    lat_g = lat_g * jnp.where(
                        crash_g > 0, frates[faults_mod.RATE_RESTART], 1.0)
                next_done = jnp.where(dispatch > 0, t_now + local(lat_g),
                                      next_done)

                # ---- 2. the event: quorum-of-K or timer, whichever first
                nd_g = gather(next_done)
                inf = jnp.float32(jnp.inf)
                t_quorum = (jnp.sort(nd_g)[jnp.clip(quorum, 1, D) - 1]
                            if has_quorum else inf)
                t_timer = t_now + timer if has_timer else inf
                t_event = jnp.minimum(t_quorum, t_timer)
                # the finiteness test keeps parked (dead) slots out of an
                # all-dead quorum event, where t_event = inf and the bare
                # <= would count every +inf slot as arrived
                arrived_g = ((nd_g <= t_event)
                             & jnp.isfinite(nd_g)).astype(jnp.float32)
                arrived_l = local(arrived_g)
                arrived_any = jnp.sum(arrived_g) > 0
                recv_g = (arrived_g * (1.0 - drop_g) if faults_on
                          else arrived_g)

                # ---- 2b. live traffic (core.stream): requests arrive
                # over this event's simulated-seconds gap into the bounded
                # per-device queues; devices that COMMITTED a round score
                # their queue and the selection cascade serves locally /
                # escalates to the fog / keeps each request queued.  All
                # draws live on the dedicated stream key; the pool only
                # advances where something escalated — zero traffic
                # replays the plain event loop bit-for-bit.
                if stream_on:
                    serve_t, esc_t, kappa, period, burst = (
                        svec[0], svec[1], svec[2], svec[3], svec[4])
                    t_next = jnp.where(jnp.isfinite(t_event), t_event,
                                       t_now)
                    dt = jnp.maximum(t_next - t_now, 0.0)
                    gids = local(jnp.arange(D, dtype=jnp.int32))
                    srates_l = local(srates)
                    if churn_on:
                        # a dead device receives no traffic
                        srates_l = srates_l * (live > 0)

                    def arrivals_one(gid, rate, labels_d, valid_d, qi, qv):
                        # per-device key folded at the GLOBAL slot index:
                        # identical traffic under any mesh factorization
                        kd = jax.random.fold_in(skey, gid)
                        k_cnt, k_pick = jax.random.split(kd)
                        n = stream_mod.draw_arrival_count(
                            s_process, k_cnt, rate, dt, burst, A_max)
                        logits = stream_mod.drift_logits(
                            labels_d, valid_d, kappa, period, t_next, ncls)
                        picks = jax.random.categorical(
                            k_pick, logits, shape=(A_max,)).astype(
                                jnp.int32)
                        ok = (jnp.arange(A_max) < n) & jnp.any(valid_d)
                        qi, qv, drp = stream_mod.queue_append(
                            qi, qv, picks, ok)
                        return qi, qv, drp, n

                    q_idx, q_valid, dropped_d, offered_d = \
                        jax.vmap(arrivals_one)(gids, srates_l, labels,
                                               valid, q_idx, q_valid)

                    def cascade_one(gid, p_d, qi, qv, lmask_d, images_d,
                                    labels_d):
                        kd = jax.random.fold_in(skey, D + gid)
                        k_score, k_rank = jax.random.split(kd)
                        x_q = jnp.take(images_d, qi, axis=0)
                        preds = jnp.argmax(eval_fn(p_d, x_q), -1)
                        if acq_random:
                            scores = jax.random.uniform(k_score, (Q,))
                        else:
                            logp = trainer.score_logprobs_raw(
                                p_d, x_q, k_score, T_mc)
                            scores = score_fn(logp)
                        rank = (jax.random.uniform(k_rank, (Q,))
                                if s_selection == "random" else scores)
                        # the random-control arm spends the SAME
                        # escalation budget on uniformly-random queued
                        # requests (no threshold gate) — the bench gate's
                        # equal-budget comparison
                        esc_thr = (jnp.float32(-jnp.inf)
                                   if s_selection == "random" else esc_t)
                        serve, escal, sel, sel_ok = \
                            cascade_mod.cascade_decide(
                                scores, rank, qi, jnp.take(lmask_d, qi),
                                qv, serve_t, esc_thr, esc_k)
                        correct = jnp.take(labels_d, qi) == preds
                        return serve, escal, sel, sel_ok, correct

                    serve_q, escal_q, sel_q, selv_q, correct_q = \
                        jax.vmap(cascade_one)(gids, params, q_idx, q_valid,
                                              pool.labeled_mask, images,
                                              labels)
                    commit_b = commit > 0
                    serve_q = serve_q & commit_b[:, None]
                    escal_q = escal_q & commit_b[:, None]
                    selv_q = selv_q & commit_b[:, None]
                    # escalation: the fog labels the request and it joins
                    # the device's training pool (active learning on
                    # traffic) — trained from the NEXT dispatch onward
                    pool_esc = jax.vmap(vpool.acquire)(pool, q_idx, sel_q,
                                                       selv_q)
                    esc_cnt_d = jnp.sum(selv_q.astype(jnp.int32), axis=1)
                    pool = _where_mask((esc_cnt_d > 0).astype(jnp.float32),
                                       pool_esc, pool)
                    q_valid = q_valid & ~(serve_q | escal_q)
                    served_d = jnp.sum(serve_q.astype(jnp.int32), axis=1)
                    correct_d = jnp.sum(
                        (serve_q & correct_q).astype(jnp.int32), axis=1)
                    depth_d = jnp.sum(q_valid.astype(jnp.int32), axis=1)

                # ---- 3. staleness-decayed Eq. 1 over the arrivals
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
                else:  # fedavg_n
                    raw = counts_g
                stale_g = gather(staleness)

                upload = (tmap(jnp.add, pending, residual) if use_ef
                          else pending)
                if compress:
                    qkeys = jax.vmap(
                        lambda k: jax.random.fold_in(k, 0x636F6D))(keys_r)
                    sent = jax.vmap(
                        lambda k, d: comms_mod.compress_tree(cc, k, d))(
                            qkeys, upload)
                    if use_ef:
                        # EF updates on actual communication only: an
                        # in-flight device transmitted nothing this event.
                        # The update uses the clean ``sent`` — wire
                        # corruption below is fog-side and must never leak
                        # into the device-side buffer.
                        residual = _where_mask(
                            arrived_l, tmap(jnp.subtract, upload, sent),
                            residual)
                else:
                    sent = upload
                if faults_on:
                    # wire corruption: received uploads only, applied
                    # AFTER the EF residual update
                    sent = faults_mod.corrupt_stacked(
                        corrupt_mode, sent, local(corrupt_g * recv_g),
                        frates[faults_mod.RATE_CORRUPT_SCALE])

                # fog-side guards: reject non-finite / norm-outlier
                # uploads and ZERO their leaves (a 0-weight NaN still
                # poisons a weighted sum); clip scales outliers back
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
                    sent = _where_mask(local(accept_g), sent,
                                       tmap(jnp.zeros_like, sent))
                else:
                    accept_g = recv_g

                w_g = agg_mod.staleness_weights(
                    raw, stale_g, accept_g, kind=decay, rate=decay_rate)
                # zero-accept event (a timer firing early, every arrival
                # dropped or rejected): aggregate NOTHING — the uniform
                # fallback of normalize_weights would fold every in-flight
                # delta in early AND leave it pending, double-applying it
                # on its real arrival
                accept_any = jnp.sum(accept_g) > 0
                w_g = jnp.where(accept_any, w_g, jnp.zeros_like(w_g))

                agg_delta = fpsum(agg_mod.aggregate_stacked(
                    sent, local(w_g), impl=agg_impl))
                if topo_on:
                    # intra-fog Eq. 1: each accepted delta folds into ITS
                    # fog group with per-group staleness-decayed alphas; a
                    # silent group keeps its model (the where discards the
                    # per-segment uniform fallback, which would fold
                    # in-flight pending deltas in early)
                    decayed = raw * agg_mod.staleness_decay(
                        stale_g, kind=decay, rate=decay_rate)
                    alpha, beta, group_any = topo_mod.two_tier_weights(
                        decayed, accept_g, group_ids, G)
                    fold = fpsum(agg_mod.aggregate_stacked(
                        sent, local(alpha), impl=agg_impl,
                        segment_ids=gid_l, num_segments=G))
                    fog_cand = tmap(lambda f, d: f + mix_rate * d, fog, fold)
                    fog_cand = tmap(
                        lambda a, b: jnp.where(group_any.reshape(
                            (-1,) + (1,) * (a.ndim - 1)), a, b),
                        fog_cand, fog)
                    # sync event: inter-fog Eq. 1 collapses the tier — the
                    # β-mixed fog base plus the FLAT staleness-decayed
                    # arrivals, broadcast back to every group (β ≡ 1.0 at
                    # G=1, so this IS the flat update bitwise)
                    base = topo_mod.group_reduce_stacked(fog, beta)
                    glob = tmap(lambda b, d: b + mix_rate * d,
                                base, agg_delta)
                    fog_sync = tmap(lambda a: jnp.broadcast_to(
                        a[None], (G,) + a.shape), glob)
                    fog_sync = tmap(
                        lambda a, b: jnp.where(accept_any, a, b),
                        fog_sync, fog)
                    fog = tmap(lambda a, b: jnp.where(sync_f > 0, a, b),
                               fog_sync, fog_cand)
                else:
                    fog_new = tmap(lambda f, d: f + mix_rate * d,
                                   fog, agg_delta)
                    fog = tmap(lambda a, b: jnp.where(accept_any, a, b),
                               fog_new, fog)

                # ---- 4. bookkeeping: re-dispatch arrivals, age the rest
                # (staleness is measured in committed model versions, so a
                # zero-arrival event ages nobody).  A delivered delta
                # clears its pending slot — the buffer holds ONLY
                # still-in-flight work (an arrival's next dispatch would
                # overwrite it anyway, but the returned state must not
                # carry already-applied deltas)
                pending = _where_mask(
                    arrived_l, tmap(jnp.zeros_like, pending), pending)
                if topo_on:
                    # staleness counts versions of the model a device
                    # dispatched FROM: its group's on local events, the
                    # global on sync events
                    aging = jnp.where(sync_f > 0, accept_any,
                                      jnp.take(group_any, gid_l))
                    aging = aging.astype(jnp.int32)
                else:
                    aging = accept_any.astype(jnp.int32)
                if churn_on:
                    # dead devices have nothing in flight to grow stale
                    aging = aging * (live > 0).astype(jnp.int32)
                staleness = jnp.where(arrived_l > 0, 0, staleness + aging)
                dispatch = arrived_l
                # an all-dead, timer-less fleet yields t_event = inf:
                # freeze the clock instead of poisoning every later event
                # (reborn devices restart it)
                t_now = jnp.where(jnp.isfinite(t_event), t_event, t_now)

                rec = {"weights": w_g, "upload_mask": arrived_g,
                       "n_labeled": counts_g, "staleness": stale_g,
                       "sim_time": t_event,
                       "arrivals": jnp.sum(arrived_g),
                       "timer_fired": jnp.logical_and(
                           jnp.isfinite(t_timer), t_timer <= t_quorum)}
                if churn_on:
                    rec["live"] = live_g
                if faults_on:
                    rec["crashed"] = crash_g
                    rec["dropped"] = drop_g * arrived_g
                    rec["corrupted"] = corrupt_g * recv_g
                if guards_on:
                    rec["rejected"] = reject_g
                    rec["clipped"] = clip_g
                    rec["upload_norms"] = norms_g
                    rec["accepted"] = accept_g
                if topo_on:
                    rec["fog_sync"] = (sync_f > 0).astype(jnp.float32)
                    rec["beta"] = beta
                    rec["group_accept"] = jax.ops.segment_sum(
                        accept_g, group_ids, num_segments=G)
                if stream_on:
                    rec["offered"] = jnp.sum(
                        gather(offered_d.astype(jnp.float32)))
                    rec["stream_dropped"] = jnp.sum(
                        gather(dropped_d.astype(jnp.float32)))
                    rec["served"] = jnp.sum(
                        gather(served_d.astype(jnp.float32)))
                    rec["serve_correct"] = jnp.sum(
                        gather(correct_d.astype(jnp.float32)))
                    rec["escalated"] = jnp.sum(
                        gather(esc_cnt_d.astype(jnp.float32)))
                    rec["queue_depth"] = gather(
                        depth_d.astype(jnp.float32))
                if has_val:
                    rec["device_accs"] = accs_g
                    # cloud-side estimate: the slot-share-weighted fog mix
                    # (== the fog model itself at G=1)
                    eval_model = (topo_mod.group_reduce_stacked(fog, gfrac)
                                  if topo_on else fog)
                    preds = jnp.argmax(eval_fn(eval_model, val_x), -1)
                    rec["agg_acc"] = jnp.mean(
                        (preds == val_y).astype(jnp.float32))
                out = (fog, params, opt_state, pool, rng, residual,
                       pending, staleness, next_done, dispatch,
                       t_now, live)
                if stream_on:
                    out = out + (q_idx, q_valid)
                return out, rec

            # prologue encoded as carry init: everyone is freshly
            # dispatched the fog model (= any state row — init/set_params
            # broadcast identical rows) at t = 0.  With a topology the
            # [G, ...] fog stack is rebuilt from one exact representative
            # row per group (rows within a group are identical by the
            # dispatch protocol; the one-hot segment-sum + fleet psum
            # recovers them under any mesh factorization)
            if topo_on:
                fidx = jax.ops.segment_min(
                    jnp.arange(D, dtype=jnp.int32), group_ids,
                    num_segments=G)
                repr_l = local(jnp.zeros((D,), jnp.float32)
                               .at[fidx].set(1.0))
                fog0 = fpsum(topo_mod.segment_sum_stacked(
                    state.params, repr_l, gid_l, G))
            else:
                fog0 = tmap(lambda a: a[0], state.params)
                if has_excl:
                    # excluded leaves may differ per device when chaining
                    # a previous run: the fog carries GLOBAL slot 0's copy
                    # (one-hot + fleet psum — ``a[0]`` is shard-LOCAL row 0
                    # under shard_map, the aggregation.py caveat)
                    rep0_l = local(
                        jnp.zeros((D,), jnp.float32).at[0].set(1.0))
                    fog0 = twp(
                        lambda kp, s, b: (fpsum(jnp.tensordot(
                            rep0_l, s, axes=1)) if _is_excl(kp) else b),
                        state.params, fog0)
            carry = (fog0, state.params, state.opt_state, state.pool,
                     state.rng, state.residual, state.pending,
                     state.staleness,
                     jnp.zeros((D_local,), jnp.float32),
                     jnp.ones((D_local,), jnp.float32),
                     jnp.float32(0.0), state.live)
            if stream_on:
                # the live-traffic queues start empty
                carry = carry + (jnp.zeros((D_local, Q), jnp.int32),
                                 jnp.zeros((D_local, Q), bool))
            xs_rows = (keys_all, lat_keys, fkeys)
            if topo_on:
                xs_rows = xs_rows + (sync_flags,)
            if stream_on:
                xs_rows = xs_rows + (skeys,)
            carry, recs = jax.lax.scan(one_event, carry, xs_rows)
            (fog, params, opt_state, pool, rng, residual, pending,
             staleness, _nd, _disp, _t, live) = carry[:12]
            out_state = type(state)(params, opt_state, pool, rng,
                                    residual, pending, staleness, live)
            return out_state, recs, fog

        if on_mesh:
            dev = _fleet_spec(mesh)
            events_all = jax.shard_map(
                events_all, mesh=mesh,
                # fkeys / frates / gfactor / group_ids / sync_flags /
                # skeys / srates / svec replicate: fault draws, the
                # topology, and the traffic process are global-fleet
                # facts every shard derives identically (per-device
                # stream keys fold at GLOBAL slot ids)
                in_specs=(dev, dev, dev, dev, P(), P(), P(), P(),
                          _fleet_spec(mesh, None), P(), P(), P(), P(),
                          P(), P(), dev, P(), P(), P(), P(), P(), P(),
                          P()),
                # recs and the fog model are replicated (all_gather / psum
                # results); state stays sharded
                out_specs=(dev, P(), P()), check_vma=False)

        return jax.jit(events_all, donate_argnums=_donate_argnums(0))

    key = engine._cache_key("async_events", False) + (
        events, aggregation, comms_key, async_key, faults_key, guards_key,
        churn_mode, topo_key, stream_key, hetero_steps, excl_paths)
    return _compiled(key, build)


def run_events_fused(engine, state, events: int, *,
                     async_cfg: Optional[AsyncConfig] = None,
                     aggregation: str = "fedavg_n",
                     comms=None, start_event: int = 0,
                     faults=None, guards=None, topology=None,
                     stream=None, hetero=None, fleet=None):
    """``events`` fog aggregation events — rounds-free FedAsync/FedBuff
    dynamics — in ONE dispatch.

    ``engine`` is an ``EdgeEngine`` (optionally mesh-sharded); ``state`` an
    ``EngineState`` whose param rows are identical (the init/re-dispatch
    protocol every driver follows).  ``aggregation`` ∈ average | weighted |
    fedavg_n — ``optimal`` is argmax selection with no Eq. 1 weights for
    staleness decay to act on, and is rejected (same contract as hetero).
    ``comms`` (``core.comms.CommsConfig``) compresses each uploaded delta
    in-compile with error-feedback residuals in ``state.residual``.

    Chaining: a second call continues the fog model, pools, residuals,
    and staleness counters, but RESTARTS the virtual clock — every device
    is freshly dispatched at t = 0 (the prologue), so work that was still
    in flight when the previous call ended is re-run from the new
    dispatch, not delivered.  Pass ``start_event`` = events completed so
    far so the key and latency schedules don't replay the first call's
    randomness (the ``run_rounds_fused(start_round=...)`` stale-seed
    contract).

    Returns ``(state, recs, fog_params)``:

    * ``state`` — the final fleet state; ``pending`` holds each device's
      still-in-flight delta and ``staleness`` its age in model versions;
    * ``recs`` — per-event telemetry stacked over the leading event axis:
      ``sim_time`` (simulated seconds of each aggregation event),
      ``upload_mask`` (the arrivals), ``arrivals`` (their count),
      ``timer_fired`` (whether the timer beat the quorum), ``weights``
      (the staleness-decayed Eq. 1 alphas), ``staleness`` (pre-aggregation
      ages), ``n_labeled``, and — when the engine has a validation set —
      ``device_accs`` / ``agg_acc``;
    * ``fog_params`` — the fog model after the last event.

    With ``async_cfg.mean_latency == 0`` (and ``device_means`` unset/zero)
    and ``quorum >= D``, every event is a full barrier and the result
    matches ``run_rounds_fused`` ≤ 1e-5.

    ``topology`` (``core.topology.FogTopology``) runs the event loop over
    the two-tier fog hierarchy: arrivals fold into their OWN fog group's
    model every event (intra-fog Eq. 1), the tier collapses to a global
    model only on every ``local_steps``-th event (inter-fog Eq. 1, the
    fog→cloud sync — between syncs no bytes cross the upper tier), the
    per-fog ``latency_scale`` profile multiplies the device latency means,
    and guards / staleness go per-group.  ``uniform_topology(D, 1)``
    reproduces the flat event loop bitwise.  Telemetry gains per-event
    ``fog_sync`` / ``beta`` / ``group_accept`` rows; ``agg_acc`` becomes
    the slot-share-weighted fog mix between syncs.  ``compute_scale``
    caps each device's fit steps at
    ``clip(round(scale · train_steps_per_acq), 1, train_steps_per_acq)``
    — slow fog groups do less local work per dispatch, the same step-limit
    surface the hetero engine exposes per device.

    ``stream`` (``core.stream.StreamConfig``) runs live traffic on the
    virtual clock: unlabeled requests arrive per device over each event's
    simulated-seconds gap (Poisson or deterministic rate, optional bursts
    and temporal label drift), land in bounded per-device queues, and —
    on the device's next committed round — are scored by the acquisition
    scorer and split by the selection cascade
    (``core.cascade.cascade_decide``) into served-locally, escalated to
    the fog (labeled there and added to the training pool, billed as
    uplink sample bytes), or kept queued until backpressure drops them.
    Telemetry gains per-event ``offered`` / ``stream_dropped`` /
    ``served`` / ``serve_correct`` / ``escalated`` scalars and a
    ``queue_depth [D]`` row (``core.stream.stream_telemetry`` summarizes
    them).  With ``stream=None`` the traffic program is not traced at
    all; a StreamConfig with zero arrival rate DOES trace it and
    reproduces the plain event loop bitwise (the reduction contract
    pinned by ``tests/test_stream.py``).

    ``hetero`` (``core.hetero.HeteroConfig``) maps its COMPUTE profile
    onto the event loop: ``slow_fraction`` / ``step_limits`` feed the
    same traced ``[D]`` step-limit vector the sync engine masks local
    fit steps with, min-composed with any topology ``compute_scale``
    budget — one config describes both engines.  ``straggler_rate > 0``
    is rejected (the event loop's latency model IS the straggler model);
    the ``decay``/``buffer_stale`` fields are sync-round staleness
    semantics and are ignored here (``async_cfg.decay`` governs).

    ``fleet`` (``core.fleet.FleetConfig``) bundles ``comms``/
    ``async_cfg``/``faults``/``guards``/``topology``/``stream``/
    ``hetero`` as one value; the per-feature kwargs keep working and may
    not be mixed with ``fleet=`` without a warning (legacy values win).

    ``faults`` / ``guards`` (``core.faults``) inject event-time faults and
    enable the fog-side aggregation guards — see
    ``EdgeEngine.run_rounds_fused`` for the shared surface.  Async churn
    is always the in-trace birth/death process (event time has no host
    round schedule to key a ``live_mask`` against): dead devices park
    their queue slot at ``+inf`` and cannot arrive; reborn slots are
    freshly dispatched the current fog model.  A crash loses the round's
    work AND multiplies the completion latency by ``faults.restart_mult``.
    """
    fleet = fleet_mod.resolve_fleet(
        fleet, "run_events_fused",
        allowed=("comms", "async_cfg", "faults", "guards", "topology",
                 "stream", "hetero"),
        comms=comms, async_cfg=async_cfg, faults=faults, guards=guards,
        topology=topology, stream=stream, hetero=hetero)
    comms, async_cfg, faults = fleet.comms, fleet.async_cfg, fleet.faults
    guards, topology, stream = fleet.guards, fleet.topology, fleet.stream
    hetero = fleet.hetero
    if async_cfg is None:
        raise ValueError("run_events_fused needs an AsyncConfig "
                         "(async_cfg= or fleet.async_cfg)")
    if aggregation not in _ASYNC_AGGREGATIONS:
        raise ValueError(
            f"async aggregation must be one of "
            f"{' | '.join(_ASYNC_AGGREGATIONS)}, got {aggregation!r} "
            f"('optimal' has no Eq. 1 weights for staleness decay)")
    if aggregation == "weighted" and engine.test_images is None:
        raise ValueError(
            "aggregation='weighted' scores devices on a validation set; "
            "construct EdgeEngine with test_set")
    engine._check_capacity(
        state, rounds=events,
        extra_per_round=(stream.escalate_k if stream is not None else 0))
    D = engine.num_devices
    if topology is not None:
        topology.validate_for(D)
    if hetero is not None and hetero.straggler_rate > 0.0:
        raise ValueError(
            "hetero.straggler_rate has no event-time meaning: the async "
            "latency model IS the straggler model (AsyncConfig.dist / "
            "mean_latency / latency_skew / device_means).  Set "
            "straggler_rate=0 — only the compute profile (slow_fraction / "
            "step_limits) maps onto the event loop")

    comms_key = None
    wire = ("float32" if comms is None
            else getattr(comms, "compute_dtype", "float32"))
    if comms is not None and (comms.compression != "none"
                              or wire != "float32"):
        comms_key = (comms.compression, comms.topk_fraction,
                     comms.error_feedback, wire)
        if comms.error_feedback and not jax.tree_util.tree_leaves(
                state.residual):
            state = state._replace(residual=jax.tree_util.tree_map(
                jnp.zeros_like, state.params))
    if comms_key is None or not comms_key[2]:
        state = state._replace(residual=())

    # pending (in-flight deltas) and staleness (model-version ages) are the
    # event loop's working state.  The prologue freshly dispatches EVERY
    # device at t = 0, so ages start at zero — carried staleness (from a
    # previous call or a hetero run) would wrongly decay event-0 uploads —
    # and any carried pending is overwritten by the first dispatch before
    # the first aggregation reads it.
    if not jax.tree_util.tree_leaves(state.pending):
        state = state._replace(pending=jax.tree_util.tree_map(
            jnp.zeros_like, state.params))
    state = state._replace(staleness=jnp.zeros((D,), jnp.int32))
    # fault statics + liveness hygiene (the run_rounds_fused contract:
    # churn is "process" whenever faults are on, zero rates stay fully
    # live; with faults off any carried liveness is dropped)
    if guards is not None and guards.policy == "off":
        guards = None
    churn_mode = "process" if faults is not None else "none"
    if churn_mode != "none":
        if not jax.tree_util.tree_leaves(state.live):
            state = state._replace(live=jnp.ones((D,), jnp.float32))
    else:
        state = state._replace(live=())
    faults_key = faults_mod.faults_static_key(faults,
                                              engine._num_classes())
    guards_key = faults_mod.guards_static_key(guards)
    state = engine._shard_state(state)

    async_key = (async_cfg.dist, float(async_cfg.sigma),
                 async_cfg.quorum is not None, async_cfg.timer is not None,
                 async_cfg.decay, float(async_cfg.decay_rate))
    means_np = device_latency_means(async_cfg, D)
    topo_key = None
    # one HeteroConfig describes both engines: its compute profile
    # (slow_fraction / step_limits) feeds the same traced [D] step-limit
    # vector the sync engine masks fit steps with, min-composed with any
    # per-group topology budget (a device obeys the tighter of its own
    # budget and its fog group's ceiling).  The decay/buffer fields are
    # sync-round staleness semantics — the event loop has its own
    # (AsyncConfig.decay) and ignores them.
    sl_np = (hetero_mod.device_step_limits(
        hetero, D, engine.cfg.train_steps_per_acq)
        if hetero is not None else None)
    hetero_steps = sl_np is not None
    if topology is not None:
        from repro.core import topology as topo_mod
        topo_key = (topology.num_groups, int(topology.local_steps),
                    topology.compute_scale is not None)
        means_np = topo_mod.topology_latency_means(topology, means_np)
        sl_np = topo_mod.topology_step_limits(
            topology, D, engine.cfg.train_steps_per_acq, base=sl_np)
        group_ids = jnp.asarray(topology.ids)
        sync_rows = jnp.asarray(
            topo_mod.sync_schedule(topology, events, start_event))
    else:
        group_ids = jnp.zeros((D,), jnp.int32)
        sync_rows = jnp.ones((events,), jnp.float32)
    means = jnp.asarray(means_np)
    step_limits = jnp.asarray(
        sl_np if sl_np is not None
        else np.full((D,), engine.cfg.train_steps_per_acq, np.int32))
    stream_k = stream_mod.stream_static_key(stream)
    if stream is not None:
        srates = jnp.asarray(stream_mod.device_arrival_rates(stream, D))
        skeys = stream_mod.stream_keys(stream, start_event, events)
        svec = jnp.asarray([stream.serve_threshold,
                            stream.escalate_threshold,
                            stream.drift_kappa, stream.drift_period,
                            stream.burst], jnp.float32)
    else:
        srates = jnp.zeros((D,), jnp.float32)
        skeys = jax.random.split(jax.random.key(0), events)
        svec = jnp.zeros((5,), jnp.float32)
    # event 0 consumes the incoming state's keys; later events follow the
    # absolute-index schedule (the run_rounds_fused chaining contract)
    later = [engine.device_keys(start_event + t) for t in range(1, events)]
    keys_all = (jnp.stack([state.rng] + later) if later
                else state.rng[None])
    lat_base = jax.random.key(async_cfg.seed + 0x6C6174)
    lat_keys = jax.vmap(lambda t: jax.random.fold_in(lat_base, t))(
        jnp.arange(start_event, start_event + events))
    quorum = jnp.int32(async_cfg.quorum if async_cfg.quorum is not None
                       else D)
    timer = jnp.float32(async_cfg.timer if async_cfg.timer is not None
                        else 0.0)
    fkeys = (faults_mod.fault_keys(faults, start_event, events)
             if faults is not None
             else jax.random.split(jax.random.key(0), events))
    frates = jnp.asarray(faults_mod.rates_vector(faults))
    gfactor = jnp.float32(guards.norm_factor if guards is not None
                          else 0.0)
    fn = _get_async_jit(engine, events, aggregation, comms_key, async_key,
                        faults_key, guards_key, churn_mode, topo_key,
                        stream_key=stream_k, hetero_steps=hetero_steps,
                        excl_paths=engine._exclude_paths(state.params))
    counters.count_dispatch()
    state, recs, fog = fn(state, engine.images, engine.labels,
                          engine.valid,
                          engine.seed_images, engine.seed_labels,
                          engine.test_images, engine.test_labels,
                          keys_all, lat_keys, skeys, means, quorum, timer,
                          jnp.float32(async_cfg.mix_rate), step_limits,
                          srates, svec, fkeys, frates,
                          gfactor, group_ids, sync_rows)
    return state, recs, fog


def async_telemetry(recs) -> dict:
    """Host-side wall-clock-vs-accuracy telemetry from the fused event
    recs: simulated-seconds trajectory (not just event counts), arrival
    statistics, and staleness summary."""
    from repro.core.hetero import summarize_staleness

    sim = np.asarray(recs["sim_time"], np.float64)
    arrivals = np.asarray(recs["arrivals"], np.float64)
    out = {
        "events": int(sim.shape[0]),
        "sim_seconds_total": float(sim[-1]) if sim.size else 0.0,
        "sim_time_per_event": [float(t) for t in sim],
        "mean_arrivals_per_event": float(arrivals.mean()),
        "timer_fired_events": int(np.asarray(recs["timer_fired"]).sum()),
        "staleness": summarize_staleness(recs["staleness"]),
    }
    if "agg_acc" in recs:
        accs = np.asarray(recs["agg_acc"], np.float64)
        out["final_acc"] = float(accs[-1])
        out["accuracy_vs_sim_time"] = [
            {"event": t, "sim_seconds": float(sim[t]),
             "accuracy": float(accs[t])}
            for t in range(sim.shape[0])
        ]
    return out


def report_telemetry(round_reports) -> dict:
    """The same wall-clock-vs-accuracy summary as ``async_telemetry``, built
    from the per-event report dicts ``run_federated_rounds(engine="async")``
    emits (the ``run_experiment`` contract: every async repeat carries an
    ``"async"`` telemetry entry).  Reassembles the stacked recs the reports
    were flattened from and delegates — one summary implementation."""
    return async_telemetry({
        "sim_time": [r["sim_time"] for r in round_reports],
        "arrivals": [r["arrivals"] for r in round_reports],
        "timer_fired": [r["timer_fired"] for r in round_reports],
        "staleness": [r["staleness"] for r in round_reports],
        "agg_acc": [r["aggregated_acc"] for r in round_reports],
    })
