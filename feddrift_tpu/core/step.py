"""The federated round as a single XLA program.

Reference hot path (SURVEY.md §3.2): server broadcasts M state_dicts to C
client processes over MPI; each client runs, per model, ``epochs`` SGD steps
on randomly sampled batches (FedAvgEnsTrainer.py:50-85, weighted time-step
sampling in FedAvgEnsTrainerSoftCluster.py:72-125); the server then does a
per-model sample-weighted parameter average skipping unused models
(FedAvgEnsAggregatorSoftCluster.py:149-185).

Here the whole round is ONE jitted function:

    params      [M, ...]        model pool (replicated over the mesh)
    opt_state   [M, C, ...]     per-(model, client) optimizer state; persists
                                across rounds within a time step, reset at
                                step boundaries — exactly the lifetime of the
                                reference's per-process optimizers
    x, y        [C, T1, N, ...] the full drift dataset (sharded over clients)
    time_w      [M, C, T1]      per-(model, client) time-step sampling weights
                                (the sc_weights tensor, FedAvgEnsDataLoader.py:589)
    sample_w    [M, C, N]       per-sample weights (KUE Poisson bootstrap;
                                ones otherwise)
    feat_mask   [M, F]          multiplicative feature masks (KUE; ones otherwise)
    lr_scale    []              dynamic LR multiplier (Adaptive-FedAvg)

Local SGD vmaps over (M, C); aggregation is a weighted mean over the client
axis, which GSPMD lowers to an all-reduce over ICI when C is sharded. Unused
(model, client) pairs (zero total weight) still execute — static shapes — but
their updates are masked out, mirroring the reference's skip logic
(FedAvgEnsTrainerSoftCluster.py:67-79, AggregatorSoftCluster.py:151-169).
Where the caller counted on the host that no client trains more than K < M
models this round (``train_round``'s ``models_per_client``), the vmap runs
over (K, C) instead — `_round_compact`: a hard assignment trains C pairs,
not M x C. Where the caller knows from the algorithm that only W trailing
time steps carry weight (``train_round``'s ``time_window``), the program
slices x, y and time_w to them first and every body sees T1 = W.

Batch sampling semantics match the reference: data is pre-shuffled once per
time step (host side), a step picks time step t ~ Categorical(time_w) and a
contiguous batch within it (FedAvgEnsTrainerSoftCluster.py:91-113: concatenated
per-step batch lists, uniform batch choice). With per-sample weights the batch
is instead drawn by weighted categorical sampling with replacement (the
Poisson bootstrap resample, retrain.py:65-74).

Population mode (cfg.population_size > 0) changes nothing here by design:
the client axis C is the sampled COHORT, and the runner re-gathers a new
cohort's shard into identically-shaped x/y stacks each iteration
(simulation/runner.py::_prepare_cohort). Stragglers and quorum-degraded
rounds arrive as the same client_mask rows subsampling always used (an
all-zero row = keep-prev-params no-op via the masked aggregation), so the
registered population can grow 10^2 -> 10^5 without a single new argument
signature — the compile-count invariance the _note_signature detector and
the POPSCALE regress axis gate.
"""

from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from feddrift_tpu import obs
from feddrift_tpu.comm.compress import simulate_codec
from feddrift_tpu.core.functional import confusion_matrix, cross_entropy, tree_select
from feddrift_tpu.core.precision import (PrecisionPolicy, cast_floating,
                                         match_dtypes)
from feddrift_tpu.parallel.mesh import constrain_pool
from feddrift_tpu.platform.faults import BYZ_MODES, apply_byzantine_updates
from feddrift_tpu.platform.hierarchical import two_tier_aggregate
from feddrift_tpu.resilience.robust_agg import (RobustAggConfig, _stats,
                                                 aggregate)
from feddrift_tpu.utils.prng import iteration_key


class StackOperands(NamedTuple):
    """The operands of a round that need the [M, C, ...] parameter stack or
    `aggregate`'s own closing of the round: one pytree, a None field an
    empty subtree, as a None argument is. ``train_round`` takes a round's
    rows; the fused entries take them with a leading [R] (megastep: [K, R])
    axis and keep the two carries, ``stale_params`` and ``codec_prev``,
    inside their scan (None here).

    byz_modes [C] int32 (platform/faults.BYZ_MODES, 0 = honest): adversary
    injection. label_flip corrupts the training labels before local SGD,
    every other mode the submitted update stack after it, BEFORE
    aggregation, so the server-side defense sees what a malicious client
    would send. stale_params [M, C, ...]: each client's previous-round
    submission, needed only when stale_replay can occur.
    edge_ids [C] int32 / edge_mask [E] / edge_modes [E]: the two-tier
    hierarchy (platform/hierarchical.py::two_tier_aggregate), read only
    when ``hier_edges > 0``. codec_prev [M, C, ...]: last round's decoded
    diff stack, the delta codec's carry (None -> zeros: round 0 deltas
    against the broadcast params).
    """
    byz_modes: object = None
    stale_params: object = None
    edge_ids: object = None
    edge_mask: object = None
    edge_modes: object = None
    codec_prev: object = None


def weight_cdf(weights: jnp.ndarray) -> jnp.ndarray:
    """Normalized inclusive cumsum of non-negative weights, for
    ``inverse_cdf_draw``."""
    cdf = jnp.cumsum(weights)
    return cdf / cdf[-1]


def inverse_cdf_draw(key, cdf: jnp.ndarray, batch: int) -> jnp.ndarray:
    """Sample ``batch`` indices i with P(i) = cdf[i] - cdf[i-1].

    Inverse-CDF sampling: B uniforms + a B*log(K) binary search, replacing
    the per-draw Gumbel categorical (B*K noise + argmax) that was KUE's
    measured hot op (round-2 verdict item 7). side="right" maps
    u in [cdf[i-1], cdf[i]) to i, so zero-weight cells (including leading
    zeros at u=0) are never selected; the clip is a numerical backstop for
    u == 1.0 - eps rounding.
    """
    u = jax.random.uniform(key, (batch,))
    return jnp.clip(jnp.searchsorted(cdf, u, side="right"),
                    0, cdf.shape[0] - 1)


def time_window_of(x, y, time_w, lo, width: int):
    """The ``width`` time steps from ``lo`` on of a round's data and of its
    time weights: x, y [C, T1, ...] -> [C, width, ...], time_w [M, C, T1] ->
    [M, C, width]. ``lo`` is an operand, so one program serves every time
    step; ``width`` is static."""
    return (jax.lax.dynamic_slice_in_dim(x, lo, width, axis=1),
            jax.lax.dynamic_slice_in_dim(y, lo, width, axis=1),
            jax.lax.dynamic_slice_in_dim(time_w, lo, width, axis=2))


def make_optimizer(name: str, lr: float, wd: float) -> optax.GradientTransformation:
    """Client optimizer. Reference: SGD(lr) or Adam(lr, wd, amsgrad=True)
    (FedAvgEnsTrainer.py:28-33)."""
    if name == "sgd":
        return optax.sgd(lr)
    return optax.chain(optax.add_decayed_weights(wd), optax.amsgrad(lr))


# eq=False keeps the dataclass hashable (identity hash) so jit can treat
# `self` as a static argument.
@dataclass(eq=False)
class TrainStep:
    """Compiled train/eval programs for one (module, dataset geometry)."""

    apply_fn: Callable          # (params, x) -> logits
    optimizer: optax.GradientTransformation
    batch_size: int
    num_steps: int              # local SGD steps per round (reference `epochs`)
    num_classes: int
    # Static: per-sample weighted batch sampling (KUE's Poisson bootstrap,
    # retrain.py:65-74). When False (every other algorithm: sample_w == 1)
    # the B-draw categorical over the flattened [T1*N] axis — by far the most
    # expensive op of a small-model round — is never emitted.
    weighted_sampling: bool = False
    # Static: which per-cluster aggregator closes the round
    # (resilience/robust_agg.py registry; "mean" is bitwise-identical to
    # the historical inline weighted average) and its knobs. Static so the
    # round program specializes — the robust paths (sorts, Krum distance
    # matrices) are only ever emitted when actually selected.
    robust_agg: str = "mean"
    robust_cfg: RobustAggConfig = field(default_factory=RobustAggConfig)
    # Static Byzantine attack magnitudes (platform/faults.py modes); only
    # read when a byz_modes vector is passed into the round.
    byz_scale: float = 10.0
    byz_std: float = 1.0
    # Static: two-tier hierarchical aggregation (platform/hierarchical.py).
    # hier_edges > 0 replaces the flat aggregation with client -> edge ->
    # server: edge_agg within each group, server_agg across the edge
    # summaries — both drawn from the same robust_agg registry. The edge
    # loop is Python-unrolled, so the round program specializes on E.
    hier_edges: int = 0
    edge_agg: str = "mean"
    server_agg: str = "mean"
    # Static: in-program wire-codec simulation (comm/compress.py): the
    # submitted update stack becomes decode(encode(update)) before any
    # aggregation, so the training trajectory reflects exactly the loss
    # the negotiated codec introduces on the broker path.
    codec: str = "none"
    codec_topk_frac: float = 0.4
    # Static: the end-to-end precision policy (core/precision.py). The
    # pool/opt-state dtype is whatever the caller stores them at
    # (param_dtype by contract); inside the round program the policy
    # drives two boundaries: the aggregation inputs/outputs (agg_dtype in,
    # param_dtype out — the "accumulate in f32, store in bf16" recipe) and
    # the [E, M, C] eval-loss buffers + their scan carries (eval_dtype).
    # Every cast site is a same-dtype identity under the default f32
    # policy, so the emitted XLA is bit-for-bit the historical program.
    precision: PrecisionPolicy = field(default_factory=PrecisionPolicy)
    # Static: XLA cost-capture level for the tracked programs
    # (obs/costmodel.py CAPTURE_LEVELS). "lowered" re-lowers each program
    # once at first compile to read cost_analysis() (FLOPs / bytes
    # accessed); "compiled" additionally compiles the lowered module for
    # memory_analysis() (exact static HBM) — one extra XLA compile per
    # program, which bench.py opts into.
    cost_capture: str = "lowered"
    # Static: how the round programs run the (model, client) pairs
    # (cfg.client_axis). "vmap" is the body the docstring above describes.
    # "scan" (`_round_body_scan`) takes the models and, within a model, its
    # clients one at a time, skips a pair whose time weights sum to 0 and
    # adds each trained pair's parameters at once into one running weighted
    # sum: no [M, C, ...] parameter, gradient or optimizer stack exists at
    # any point, so a model whose M x C copies do not fit can train.
    # `acc_matrix` then evaluates one forward at a time the same way.
    client_axis: str = "vmap"
    # Optional (params, x) -> (logits, stats): the forward that also returns
    # the model's own counts of the call (models/mla_moe.py: the tokens and
    # the assignments its held experts got). Read by the scanned body alone,
    # which returns their sum over the round's training steps.
    stats_fn: Callable | None = None
    # Optional (logits, y) -> loss and (logits, y) -> (hits, summed loss,
    # labels): a model's own training objective and evaluation counts
    # (models/sdar_moe.py: the masked-diffusion loss, whose labels are the
    # masked positions). None: mean cross-entropy, a hit and a label per
    # label of ``y``.
    objective: Callable | None = None
    eval_counts: Callable | None = None
    # Optional device mesh (parallel/mesh.py). When it names a "models"
    # and/or "clients" axis, the megastep program annotates its carry
    # params / opt states / time-weight slices with with_sharding_constraint
    # so GSPMD keeps the 2-D (models, clients) layout through the scan.
    # None (or a mesh naming neither axis) leaves every program untouched.
    # `self` is a static jit argument (identity hash), so setting this
    # before first dispatch is compile-safe.
    mesh: object = field(default=None, repr=False)
    # Compile tracking: per jitted entry point, the set of argument
    # signatures (leaf shapes/dtypes + static values) seen so far. jit
    # retraces exactly when the signature is new, so a second distinct
    # signature on the same entry point IS a recompile — including the
    # donated-buffer programs, where a silent recompile also doubles the
    # transient HBM for the donated args.
    _signatures: dict = field(default_factory=dict, repr=False)

    def _note_signature(self, fn: str, *trees, static=()) -> str | None:
        """Record the call signature; emits jit_compile on first sight and
        jit_recompile when a DIFFERENT signature was seen before. O(leaves)
        host work per dispatch — microseconds against a multi-ms round.
        Returns the event kind emitted, or None for an already-seen
        signature (callers hook program-cost capture on "jit_compile")."""
        # shape + dtype + sharding/committed-ness: jit also keys its cache
        # on placement, so two calls with identical shapes but e.g. an
        # uncommitted first-params vs a NamedSharding-committed steady
        # state retrace silently — exactly what this tracker must surface
        sig = tuple(static) + tuple(
            (leaf.shape, str(getattr(leaf, "dtype", type(leaf).__name__)),
             str(getattr(leaf, "sharding", "")),
             bool(getattr(leaf, "committed", False)))
            if hasattr(leaf, "shape") else repr(leaf)
            for tree in trees for leaf in jax.tree_util.tree_leaves(tree))
        seen = self._signatures.setdefault(fn, set())
        if sig in seen:
            return None
        kind = "jit_compile" if not seen else "jit_recompile"
        seen.add(sig)
        obs.registry().counter("jit_compiles", fn=fn).inc()
        if kind == "jit_recompile":
            obs.registry().counter("jit_recompiles", fn=fn).inc()
        obs.emit(kind, fn=fn, signature_count=len(seen))
        return kind

    def _capture_cost(self, kind: str | None, fn: str, jit_fn, args: tuple,
                      kwargs: dict | None = None) -> None:
        """Harvest XLA cost/memory accounting on the FIRST compile of each
        tracked program (obs/costmodel.py). First compile only: the capture
        re-lowers the program, so doing it per recompile would double every
        retrace the jit_recompile event exists to flag."""
        if kind != "jit_compile" or self.cost_capture == "off":
            return
        obs.costmodel.capture(fn, jit_fn, (self,) + args, kwargs,
                              level=self.cost_capture)

    @contextlib.contextmanager
    def _tracked(self, fn: str, jit_fn, args: tuple,
                 kwargs: dict | None = None, *, sig: tuple, static=()):
        """Around the one call into a jitted program: a ``dispatch`` span
        from before the signature check to the return of the (asynchronous)
        jitted call. ``sig`` are the argument trees the signature is taken
        from. ``track_us`` on the span is the time spent in the signature
        check and the cost capture; the first dispatch of a signature traces
        and compiles synchronously, so a span that carries ``event`` is that
        compile's cost.

        A context manager and not a helper that makes the call: the jitted
        call stays in its wrapper's own frame. One more Python frame under
        it cost 20 s of tracing and lowering in the first time step on the
        chip's host (PERF.md section 6, PR 25)."""
        with obs.spans.span("dispatch", cat="round", fn=fn) as sp:
            p0 = time.perf_counter()
            kind = self._note_signature(fn, *sig, static=static)
            self._capture_cost(kind, fn, jit_fn, args, kwargs)
            track_us = round((time.perf_counter() - p0) * 1e6, 1)
            yield sp
            if kind is None:
                sp.set(track_us=track_us)
            else:
                sp.set(track_us=track_us, event=kind)

    # ------------------------------------------------------------------
    def init_opt_states(self, params, num_models: int, num_clients: int):
        """[M, C, ...] optimizer states, fresh at each time-step boundary.

        The traceable body: the megastep scan and ``eval_shape`` callers use
        it as it is. Called eagerly it is one dispatch per op and leaf; the
        runner dispatches it as one program, ``fresh_opt_states``."""
        def init_one(p):
            return self.optimizer.init(p)
        per_model = jax.vmap(init_one)(params)          # [M, ...]
        return jax.tree_util.tree_map(
            lambda s: jnp.broadcast_to(
                s[:, None], (s.shape[0], num_clients, *s.shape[1:])),
            per_model)

    def fresh_opt_states(self, params, num_clients: int):
        """``init_opt_states`` as ONE tracked program, its outputs committed
        in the placement ``train_round`` returns its optimizer states in, so
        that the round program meets one signature however often the states
        are made anew."""
        args = (params, num_clients)
        with self._tracked("fresh_opt_states",
                           type(self)._fresh_opt_states_jit, args,
                           sig=(params,), static=(num_clients,)):
            return self._fresh_opt_states_jit(*args)

    # keep_unused: only the shapes of params are read, and a pruned argument
    # brings neither its devices nor its commitment to the outputs
    @partial(jax.jit, static_argnums=(0, 2), keep_unused=True)
    def _fresh_opt_states_jit(self, params, num_clients: int):
        num_models = jax.tree_util.tree_leaves(params)[0].shape[0]
        # the clients axis alone, as GSPMD lays out train_round's states:
        # split over "models" too they come back so, the pool with them,
        # and every program that takes the pool meets a second signature
        return constrain_pool(
            self.mesh, self.init_opt_states(params, num_models, num_clients),
            model_axis=None, client_axis=1)

    # ------------------------------------------------------------------
    def _local_sgd(self, params, opt_state, key, x_ct, y_ct, w_t, s_n,
                   fmask, lr_scale, with_stats: bool = False):
        """Train ONE (model, client) pair for num_steps batches.

        x_ct: [T1, N, ...]; y_ct: [T1, N, *label_shape] (a label per sample,
        or per token of a sequence); w_t: [T1]; s_n: [N]; fmask:
        [F...]-broadcastable. ``with_stats`` (the scanned body, for a model
        with a ``stats_fn``) returns a fifth value: the model's counts,
        summed over the local steps.
        """
        T1, N = x_ct.shape[0], x_ct.shape[1]
        B = min(self.batch_size, N)
        nb = N // B                                     # batches per time step
        total_w = w_t.sum()
        active = total_w > 0

        if self.weighted_sampling:
            # Per-sample weights over the flattened [T1*N] axis:
            # p[t, n] ∝ w_t[t] * s_n[n]. Uniform fallback keeps the
            # distribution proper for inactive pairs (their result is
            # masked out below). Sampling is inverse-CDF: the cumsum is
            # computed ONCE per (model, client) round (weights are fixed
            # across the scan's steps), and each batch draw is B uniforms +
            # a B*log(T1*N) binary search — versus the per-draw Gumbel
            # categorical's B*T1*N noise+argmax, which was the measured hot
            # op of KUE rounds (round-2 verdict item 7). Same distribution,
            # different RNG realization.
            probs = jnp.where(active, 1.0, 0.0) * (w_t[:, None] * s_n[None, :])
            probs = jnp.where(probs.sum() > 0, probs, jnp.ones_like(probs))
            cdf = weight_cdf(probs.reshape(-1))
        # Time-step-level logits for contiguous-batch mode.
        wt_safe = jnp.where(total_w > 0, w_t, jnp.ones_like(w_t))
        logits_t = jnp.log(wt_safe + 1e-30)

        x_flat = x_ct.reshape((T1 * N,) + x_ct.shape[2:])
        y_flat = y_ct.reshape((T1 * N,) + y_ct.shape[2:])

        def feature_masked(xb):
            # token ids take no multiplicative feature mask
            return xb if jnp.issubdtype(xb.dtype, jnp.integer) else xb * fmask

        objective = cross_entropy if self.objective is None \
            else self.objective

        def loss_fn(p, xb, yb):
            return objective(self.apply_fn(p, feature_masked(xb)), yb)

        grad_fn = jax.value_and_grad(loss_fn)
        if with_stats:
            def loss_stats(p, xb, yb):
                logits, stats = self.stats_fn(p, feature_masked(xb))
                return objective(logits, yb), stats
            grad_fn = jax.value_and_grad(loss_stats, has_aux=True)

        def step(carry, k):
            p, o = carry
            k1, k2 = jax.random.split(k)
            if self.weighted_sampling:
                # weighted per-sample batch (with replacement)
                idx = inverse_cdf_draw(k1, cdf, B)
            else:
                # contiguous batch: t ~ Cat(w), slot ~ U[0, nb)
                t_idx = jax.random.categorical(k1, logits_t)
                slot = jax.random.randint(k2, (), 0, nb)
                idx = t_idx * N + slot * B + jnp.arange(B)
            xb, yb = x_flat[idx], y_flat[idx]
            loss, grads = grad_fn(p, xb, yb)
            loss, stats = loss if with_stats else (loss, None)
            updates, o = self.optimizer.update(grads, o, p)
            # pin the scan carry's dtypes: the f32 lr_scale operand (and
            # optax bias-correction internals) would promote bf16 updates /
            # moments to f32 mid-scan; identities under the f32 policy
            o = match_dtypes(o, opt_state)
            updates = jax.tree_util.tree_map(
                lambda u, pp: (u * lr_scale).astype(pp.dtype), updates, p)
            p = optax.apply_updates(p, updates)
            return (p, o), (loss, stats)

        keys = jax.random.split(key, self.num_steps)
        (p_new, o_new), (losses, stats) = jax.lax.scan(
            step, (params, opt_state), keys)

        p_out = tree_select(active, p_new, params)
        o_out = tree_select(active, o_new, opt_state)
        # Weighted sample count reported to the aggregator
        # (FedAvgEnsTrainerSoftCluster.py:72-74: sum_t w[t] * data volume).
        n = jnp.where(active, total_w * N, 0.0)
        if with_stats:
            return p_out, o_out, n, losses.mean(), jax.tree_util.tree_map(
                lambda a: a.sum(axis=0), stats)
        return p_out, o_out, n, losses.mean()

    # ------------------------------------------------------------------
    def _round_body(self, params, opt_states, key, x, y, time_w, sample_w,
                    feat_mask, lr_scale, client_mask=None,
                    operands=StackOperands(), slots=None):
        """One communication round (untraced body shared by train_round and
        the fused train_iteration_eval scan).

        client_mask [C] 0/1: per-round client sampling (reference
        client_sampling, AggregatorSoftCluster.py:197-205). Non-sampled
        clients train masked (total weight 0 -> params/opt untouched, n=0)
        and drop out of the aggregation, like the reference's absent ranks.
        operands: one round's `StackOperands`.

        slots (static K, 1 <= K < M): local SGD runs for K models a client
        and not for M (`_round_compact`); given by ``train_round`` alone,
        and only where nothing of the call needs the [M, C, ...] parameter
        stack (`_stack_users`), so with no operand but ``client_mask``.
        ``client_params`` is then None.

        Returns ``(new_params, new_opt, client_params, n, losses,
        agg_stats, new_codec_prev)`` — agg_stats is [M, 3] on the flat
        path and [1 + E, M, 3] (server tier in row 0) on the hierarchy
        path; new_codec_prev is None unless codec == "delta".
        """
        (byz_modes, stale_params, edge_ids, edge_mask, edge_modes,
         codec_prev) = operands
        if client_mask is not None:
            time_w = time_w * client_mask[None, :, None]
        if byz_modes is not None:
            if y.ndim > 3:
                raise ValueError(
                    "byz_modes (label_flip) reads one label a sample; y has "
                    f"trailing label axes {y.shape[3:]} (a label per token)")
            # label flipping at the data layer: y -> (K-1) - y for the
            # attackers (eval paths read the untouched dataset)
            flip = (byz_modes == BYZ_MODES["label_flip"])
            y = jnp.where(flip.reshape((-1,) + (1,) * (y.ndim - 1)),
                          self.num_classes - 1 - y, y)
        M = time_w.shape[0]
        C = x.shape[0]
        keys = jax.random.split(key, M * C).reshape(M, C, 2)
        if slots is not None:
            return self._round_compact(params, opt_states, keys, x, y, time_w,
                                       sample_w, feat_mask, lr_scale, slots)

        # vmap over clients (inner), then models (outer).
        def per_model(p_m, o_m, k_m, w_m, s_m, f_m):
            return jax.vmap(
                lambda o, k, xc, yc, w, s: self._local_sgd(
                    p_m, o, k, xc, yc, w, s, f_m, lr_scale)
            )(o_m, k_m, x, y, w_m, s_m)

        client_params, new_opt, n, losses = jax.vmap(per_model)(
            params, opt_states, keys, time_w, sample_w, feat_mask)

        if byz_modes is not None:
            client_params = apply_byzantine_updates(
                client_params, params, byz_modes, stale_params,
                jax.random.fold_in(key, 7919), self.byz_scale, self.byz_std)
            # the gauss attack adds f32 noise: JAX promotion would silently
            # widen a bf16 stack — pin it back to the pool dtype so the
            # round program's dtypes stay policy-determined
            client_params = match_dtypes(client_params, params)

        # Wire-codec simulation AFTER the adversary: the defense sees the
        # compressed version of whatever each client (honest or not) sent.
        new_codec_prev = None
        if self.codec != "none":
            diffs = jax.tree_util.tree_map(
                lambda cp, g: cp - g[:, None], client_params, params)
            if self.codec == "delta" and codec_prev is None:
                codec_prev = jax.tree_util.tree_map(jnp.zeros_like, diffs)
            decoded, new_codec_prev = simulate_codec(
                diffs, self.codec, self.codec_topk_frac, codec_prev)
            client_params = jax.tree_util.tree_map(
                lambda g, d: g[:, None] + d, params, decoded)
            client_params = match_dtypes(client_params, params)

        # Masked per-cluster aggregation over the client axis
        # (AggregatorSoftCluster.py:149-185): the registered robust_agg
        # strategy — "mean" is the historical weighted FedAvg, bit for bit.
        # With a sharded client axis the sums become ICI all-reduces.
        # hier_edges > 0 routes the same stack through the two-tier path:
        # edge_agg within each group, server_agg across edge summaries.
        # Aggregation boundary: accumulate at agg_dtype (f32 under
        # bf16_mixed — trimmed-mean/Krum sort orders must not move on a
        # half-width accumulate), store the result back at the pool dtype.
        # Under the f32 policy every cast below is a same-dtype identity,
        # so nothing is inserted into the historical program.
        agg_dt = self.precision.agg_jnp
        cp_agg = cast_floating(client_params, agg_dt)
        p_agg = cast_floating(params, agg_dt)
        n_agg = cast_floating(n, agg_dt)
        if self.hier_edges > 0 and edge_ids is not None:
            new_params, agg_stats = two_tier_aggregate(
                self.edge_agg, self.server_agg, cp_agg, n_agg, p_agg,
                edge_ids, self.hier_edges, edge_mask, edge_modes,
                jax.random.fold_in(key, 104729), self.robust_cfg,
                self.byz_scale, self.byz_std)
        else:
            new_params, agg_stats = aggregate(
                self.robust_agg, cp_agg, n_agg, p_agg,
                jax.random.fold_in(key, 104729), self.robust_cfg)
        new_params = match_dtypes(new_params, params)
        return (new_params, new_opt, client_params, n, losses, agg_stats,
                new_codec_prev)

    def _round_compact(self, params, opt_states, keys, x, y, time_w,
                       sample_w, feat_mask, lr_scale, K: int):
        """`_round_body` from the pairs' keys on, with local SGD run for K
        models a client and not for M: the same pairs on the same keys, and
        so on the same batches, in a batch of K x C where the dense body's
        is M x C. ``time_w`` comes masked by the round's participants.

        Per client its models are put in order, those with weight first
        (a stable sort: by model index), and cut to K; the host counted at
        most K with weight (``train_round``'s ``models_per_client``). A slot
        past a client's own count holds a model without weight and trains
        masked as the dense body's pairs do: parameters and optimizer state
        as they came, n = 0. What is small is gathered along the model axis
        (parameters, optimizer state, keys, weights, feature masks); ``x``
        and ``y`` stay where they are, mapped over the client axis, which
        stays the sharded one on a mesh. The weighted mean is `weighted_mean`
        term by term, summed over the slots that hold the model; a pair that
        did not run contributes ``0 * params`` there and nothing here.

        Returns what `_round_body` returns, in its layouts: the optimizer
        state the full [M, C, ...] stack with the trained slots written
        back, ``n`` and ``losses`` [M, C] (0 for a pair in no slot),
        ``client_params`` and the codec carry None.
        """
        M = keys.shape[0]
        active = time_w.sum(axis=2) > 0                        # [M, C]
        order = jnp.argsort(~active, axis=0, stable=True)
        m_idx = order[:K]                 # [K, C]: slot k of client c trains
        slot_of = jnp.argsort(order, axis=0)    # [M, C]: m_idx's inverse
        held = slot_of < K

        def along_models(leaf, idx):
            # leaf [A, C, ...], idx [B, C] -> [B, C, ...]: leaf[idx[b, c], c]
            return jnp.take_along_axis(
                leaf, idx.reshape(idx.shape + (1,) * (leaf.ndim - 2)), axis=0)

        def pick(tree):                   # [M, C, ...] -> [K, C, ...]
            return jax.tree_util.tree_map(
                lambda l: along_models(l, m_idx), tree)

        def spread(leaf_kc, rest):        # [K, C, ...] -> [M, C, ...]
            got = along_models(leaf_kc, jnp.minimum(slot_of, K - 1))
            return jnp.where(
                held.reshape(held.shape + (1,) * (got.ndim - 2)), got, rest)

        # vmap over clients (inner), then a client's slots (outer).
        def per_slot(p_k, o_k, k_k, w_k, s_k, f_k):
            return jax.vmap(
                lambda p, o, k, xc, yc, w, s, f: self._local_sgd(
                    p, o, k, xc, yc, w, s, f, lr_scale)
            )(p_k, o_k, k_k, x, y, w_k, s_k, f_k)

        p_new, o_new, n_k, loss_k = jax.vmap(per_slot)(
            jax.tree_util.tree_map(lambda l: l[m_idx], params),
            pick(opt_states), pick(keys), pick(time_w), pick(sample_w),
            feat_mask[m_idx])
        new_opt = jax.tree_util.tree_map(spread, o_new, opt_states)
        n = spread(n_k, 0.0)
        losses = spread(loss_k, 0.0)

        agg_dt = self.precision.agg_jnp
        n_agg = cast_floating(n, agg_dt)
        denom = n_agg.sum(axis=1)                              # [M]
        share = along_models(
            n_agg / jnp.maximum(denom[:, None], 1e-12), m_idx)  # [K, C]
        mine = m_idx[None] == jnp.arange(M)[:, None, None]     # [M, K, C]

        def avg(leaf_kc, leaf_m):
            tail = (1,) * (leaf_kc.ndim - 2)
            terms = leaf_kc * share.reshape(share.shape + tail)
            agg = jnp.where(mine.reshape(mine.shape + tail), terms[None],
                            0).sum(axis=(1, 2))
            keep = (denom > 0).reshape((-1,) + (1,) * (leaf_m.ndim - 1))
            return jnp.where(keep, agg, leaf_m)

        new_params = match_dtypes(jax.tree_util.tree_map(
            avg, cast_floating(p_new, agg_dt), cast_floating(params, agg_dt)),
            params)
        agg_stats = _stats((n > 0).sum(axis=1).astype(jnp.int32))
        return new_params, new_opt, None, n, losses, agg_stats, None

    def _stack_users(self, keep_client_params, operands) -> list[str]:
        """What of a round's call needs the [M, C, ...] parameter stack or
        `aggregate`'s own closing of the round, by the name of the field
        that asks for it. The scanned body refuses these
        (`_refuse_stack_users`); the compact vmap body was measured with
        none of them, and `_round_program` runs the dense body where there
        is one."""
        return [name for name, on in (
            ("keep_client_params=True", keep_client_params),
            (f"robust_agg={self.robust_agg!r}", self.robust_agg != "mean"),
            ("robust_cfg.dp_stddev > 0", self.robust_cfg.dp_stddev > 0.0),
            (f"codec={self.codec!r}", self.codec != "none"),
            ("hier_edges > 0", self.hier_edges > 0),
            *((f"the operand {name}", given is not None)
              for name, given in zip(operands._fields, operands)),
            ("weighted_sampling", self.weighted_sampling)) if on]

    def _refuse_stack_users(self, opt_states, keep_client_params, operands):
        """``client_axis="scan"`` keeps no [M, C, ...] stack; whatever needs
        one is refused by the name of the field that asks for it."""
        asked = self._stack_users(keep_client_params, operands)
        if jax.tree_util.tree_leaves(opt_states):
            asked.insert(0, "an optimizer with state (client_optimizer="
                            "'adam'): only an optimizer without state "
                            "('sgd') is kept")
        if asked:
            raise ValueError(
                "client_axis='scan' takes one (model, client) pair at a time "
                "and keeps no [M, C, ...] parameter, gradient or optimizer "
                f"stack, which {'; '.join(asked)} "
                f"need{'s' if len(asked) == 1 else ''}: use "
                "client_axis='vmap'")

    @property
    def donates_pool(self) -> bool:
        """Whether ``train_round`` writes the new pool over the ``params`` it
        is given, which nothing may read after the call: true exactly where
        the scanned program runs (`_train_round_scan_jit` donates them)."""
        return self.client_axis == "scan"

    @property
    def fuses_rounds(self) -> bool:
        """Whether the fused programs (``train_iteration_eval``,
        ``train_megastep``) exist for this step: their body is vmap's, and
        they count a label per label of ``y`` (no ``eval_counts``)."""
        return self.client_axis != "scan" and self.eval_counts is None

    def _round_program(self, num_models: int, models_per_client,
                       keep_client_params: bool, operands):
        """The jitted program ``train_round`` dispatches and its static K:
        the scanned one, or vmap's, compact where the caller counted
        1 <= K < M and the call has none of `_stack_users`, else dense (K
        None)."""
        K = models_per_client
        if K is not None and K < 1:
            raise ValueError(f"models_per_client must be at least 1, got {K}")
        if self.donates_pool:
            return type(self)._train_round_scan_jit, None
        if K is None or K >= num_models or self._stack_users(
                keep_client_params, operands):
            K = None
        return type(self)._train_round_jit, K

    def _round_body_scan(self, params, opt_states, key, x, y, time_w,
                         sample_w, feat_mask, lr_scale, client_mask=None):
        """`_round_body` with the pairs taken one at a time
        (``client_axis="scan"``): the models in turn and, within a model,
        its clients in turn.

        A pair whose time weights sum to 0 is skipped (``lax.cond``): not
        trained, n = 0, loss 0. A trained pair runs `_local_sgd` as the vmap
        body does, on the same key (the round's key split over M * C) and
        so on the same batches, and its parameters are added at once, times
        its share of the model's sample count, into the model's running sum
        (at agg_dtype): the weighted mean of ``robust_agg="mean"``, term by
        term, summed in client order. When a model's clients are done the
        sum is written over the model's slot of the pool, which the program
        owns (`_train_round_scan_jit` donates it): one pool, one model's
        sum, one pair's parameters and gradient are all that is held. A
        model no client trained keeps its parameters, as `aggregate` leaves
        it, and so does a model one of whose trained pairs' losses is not
        finite: the pool the caller handed in no longer exists, so the
        NaN/Inf half of the runner's divergence guard is kept here.
        ``client_params`` is None and the optimizer state, which has no
        leaf (`_refuse_stack_users`), is returned as it came.

        Returns the seven outputs of `_round_body` (``client_params`` and
        the codec carry None) and an eighth, the round's counts:
        ``pairs_trained`` (what the taken branches of the ``cond`` returned)
        and, where the model gives them (``stats_fn``), its own counts
        summed over the trained pairs' local steps.
        """
        if client_mask is not None:
            time_w = time_w * client_mask[None, :, None]
        M, C, N = time_w.shape[0], x.shape[0], x.shape[2]
        keys = jax.random.split(key, M * C).reshape(M, C, 2)
        agg_dt = self.precision.agg_jnp
        totals = time_w.sum(axis=2)                            # [M, C]
        n_all = jnp.where(totals > 0, totals * N, 0.0)
        share = (n_all / jnp.maximum(n_all.sum(axis=1, keepdims=True), 1e-12)
                 ).astype(agg_dt)
        with_stats = self.stats_fn is not None
        # the state of an optimizer without state: no leaf, one pair's
        opt_one = jax.tree_util.tree_map(lambda s: s[0, 0], opt_states)

        def slot(tree, m):
            return jax.tree_util.tree_map(lambda l: l[m], tree)

        stats0 = None
        if with_stats:
            B = min(self.batch_size, N)
            stats0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype),
                jax.eval_shape(lambda p, xb: self.stats_fn(p, xb)[1],
                               slot(params, 0), x[0, 0, :B]))

        def model(m, carry):
            pool, n, losses, trained, stats = carry

            def client(c, inner):
                acc, n, losses, trained, stats = inner

                def train(_):
                    # the model's slot is read here, pair by pair, so that
                    # no copy of it lives through the clients' loop
                    out = self._local_sgd(
                        slot(pool, m), opt_one, keys[m, c], x[c], y[c],
                        time_w[m, c], sample_w[m, c], feat_mask[m], lr_scale,
                        with_stats=with_stats)
                    p_new, _, n_k, loss = out[:4]
                    return (jax.tree_util.tree_map(
                        lambda a, p: a + p.astype(agg_dt) * share[m, c],
                        acc, p_new), n_k.astype(n.dtype),
                        loss.astype(losses.dtype), jnp.ones((), jnp.int32),
                        out[4] if with_stats else None)

                def skip(_):
                    return (acc, jnp.zeros((), n.dtype),
                            jnp.zeros((), losses.dtype),
                            jnp.zeros((), jnp.int32), stats0)

                acc, n_k, loss, ran, stats_k = jax.lax.cond(
                    totals[m, c] > 0, train, skip, None)
                return (acc, n.at[m, c].set(n_k), losses.at[m, c].set(loss),
                        trained + ran,
                        jax.tree_util.tree_map(jnp.add, stats, stats_k))

            acc, n, losses, trained, stats = jax.lax.fori_loop(
                0, C, client,
                (jax.tree_util.tree_map(
                    lambda l: jnp.zeros(l.shape[1:], agg_dt), pool),
                 n, losses, trained, stats))
            ran_m = n[m] > 0
            keep = ~ran_m.any() | (ran_m & ~jnp.isfinite(losses[m])).any()
            pool = jax.tree_util.tree_map(
                lambda l, a: l.at[m].set(
                    jnp.where(keep, l[m], a.astype(l.dtype))), pool, acc)
            return pool, n, losses, trained, stats

        with jax.named_scope("client_scan"):
            new_params, n, losses, trained, stats = jax.lax.fori_loop(
                0, M, model,
                (params, jnp.zeros((M, C), time_w.dtype),
                 jnp.zeros((M, C), jnp.float32), jnp.zeros((), jnp.int32),
                 stats0))
        counts = {"pairs_trained": trained}
        if with_stats:
            counts.update(stats)
        agg_stats = _stats((n > 0).sum(axis=1).astype(jnp.int32))
        return (new_params, opt_states, None, n, losses, agg_stats, None,
                counts)

    def train_round(self, params, opt_states, key, x, y, time_w, sample_w,
                    feat_mask, lr_scale, client_mask=None,
                    operands=StackOperands(), *,
                    keep_client_params: bool = True,
                    with_agg_stats: bool = False,
                    models_per_client: int | None = None,
                    time_window: tuple[int, int] | None = None):
        """One communication round. Returns (new_params [M, ...],
        new_opt_states, client_params [M, C, ...], n [M, C], mean_loss [M, C])
        plus, when ``with_agg_stats``, the robust-aggregation stats
        ([M, 3] flat, [1 + E, M, 3] hierarchical) and the delta-codec
        carry (None unless codec == "delta").

        ``keep_client_params=False`` drops the per-client parameter output
        (returned as None): only CFL-family algorithms need the [M, C, ...]
        deltas (SURVEY.md §7 hard parts), and for deep models that output
        buffer is M x C full model copies of HBM the weighted-mean reduction
        can otherwise stream through.

        ``models_per_client`` (K): the most models with weight in ``time_w``
        that any client has, as the caller counted them on the host
        (`DriftAlgorithm.models_per_client`); None says nothing. With
        1 <= K < M, and nothing in the call that `_stack_users` names, the
        vmap body runs local SGD for K x C pairs and not for M x C
        (`_round_compact`): the same results, in the same layouts, and a
        program of its own for each K. Otherwise the dense body runs, the
        program it has always been. Counter ``pairs_run`` and the
        ``dispatch`` span's ``pairs_run`` say how many pairs the dispatched
        program runs local steps for (not under ``client_axis="scan"``,
        which counts the pairs it trained itself: ``pairs_trained``).

        ``time_window`` ``(lo, W)``: the caller's word, from the algorithm
        that made the weights (`DriftAlgorithm.time_window`), that ``time_w``
        is 0 outside the W time steps from ``lo`` on. The round program then
        takes ``x[:, lo:lo+W]``, ``y[:, lo:lo+W]`` and ``time_w[..., lo:lo+W]``
        as its first act (`time_window_of`) and every body below sees
        T1 = W: the batch is gathered from W time steps of every client and
        not from all T1, which on the chip were copied into the gather's
        layout every round. W is static and ``lo`` an operand, so the time
        steps of a run meet one program. The positional ``x``, ``y`` and
        ``time_w`` stay whole whatever the window: what stands in front of
        this entry reads them (the benchmark's recorder replays ``time_w``
        over its reference's own whole ``x``). With contiguous batches and
        W = 1 the results are the whole axis's bit for bit (a pair without
        weight, n = 0, reports the loss of batches drawn from the steps it
        is handed, which nothing reads); None hands the whole axis to the
        program it has always been. Counter ``train_round_time_steps`` and
        the ``dispatch`` span's ``time_steps`` say how many time steps the
        dispatched program was handed: W, or T1.

        Under ``client_axis="scan"`` the program is `_round_body_scan`:
        ``keep_client_params`` must be False, ``params`` is DONATED (the
        new pool is written over it) and, with ``with_agg_stats``, an
        eighth output follows the seven, the round's counts
        (``pairs_trained`` and the model's own).
        """
        args = (params, opt_states, key, x, y, time_w, sample_w, feat_mask,
                lr_scale, client_mask, operands)
        M, C, T1 = time_w.shape
        program, K = self._round_program(M, models_per_client,
                                         keep_client_params, operands)
        kwargs = {"keep_client_params": keep_client_params}
        if K is not None:
            kwargs["models_per_client"] = K
        W = None
        if time_window is not None:
            lo, W = time_window
            args += (np.int32(lo),)
            kwargs["time_steps"] = W
        with self._tracked(
                "train_round", program, args, kwargs,
                sig=(params, opt_states, x, y, time_w, sample_w, feat_mask,
                     client_mask, operands),
                static=(keep_client_params, K, W)) as sp:
            out = program(self, *args, **kwargs)
            obs.registry().counter("train_round_time_steps").inc(W or T1)
            sp.set(time_steps=W or T1)
            if not self.donates_pool:
                pairs_run = (K or M) * C
                obs.registry().counter("pairs_run").inc(pairs_run)
                sp.set(pairs_run=pairs_run)
        return out if with_agg_stats else out[:5]

    @partial(jax.jit, static_argnums=0,
             static_argnames=("keep_client_params", "models_per_client",
                              "time_steps"))
    def _train_round_jit(self, params, opt_states, key, x, y, time_w,
                         sample_w, feat_mask, lr_scale, client_mask=None,
                         operands=StackOperands(), window_lo=None, *,
                         keep_client_params: bool = True,
                         models_per_client: int | None = None,
                         time_steps: int | None = None):
        if time_steps is not None:
            x, y, time_w = time_window_of(x, y, time_w, window_lo, time_steps)
        out = self._round_body(params, opt_states, key, x, y, time_w,
                               sample_w, feat_mask, lr_scale, client_mask,
                               operands, models_per_client)
        return out if keep_client_params else (*out[:2], None, *out[3:])

    # the scanned round writes the new pool over the old one: the pool is
    # DONATED (argnum 1), and the caller's ``params`` do not outlive the call
    @partial(jax.jit, static_argnums=0, donate_argnums=(1,),
             static_argnames=("keep_client_params", "time_steps"))
    def _train_round_scan_jit(self, params, opt_states, key, x, y, time_w,
                              sample_w, feat_mask, lr_scale, client_mask=None,
                              operands=StackOperands(), window_lo=None, *,
                              keep_client_params: bool = True,
                              time_steps: int | None = None):
        self._refuse_stack_users(opt_states, keep_client_params, operands)
        if time_steps is not None:
            x, y, time_w = time_window_of(x, y, time_w, window_lo, time_steps)
        return self._round_body_scan(
            params, opt_states, key, x, y, time_w, sample_w, feat_mask,
            lr_scale, client_mask)

    @staticmethod
    def eval_rounds(R: int, freq: int) -> list[int]:
        """The reference's eval cadence: every ``frequency_of_the_test``
        rounds plus the final round (AggregatorSoftCluster.py:211)."""
        rounds = list(range(0, R, freq))
        if rounds[-1] != R - 1:
            rounds.append(R - 1)
        return rounds

    def train_iteration_eval(self, params, opt_states, iter_key, x, y, time_w,
                             sample_w, feat_mask, lr_scale, R: int, freq: int,
                             t, client_masks=None, operands=StackOperands(),
                             *, byz_stale: bool = False,
                             with_agg_stats: bool = False):
        """ALL R communication rounds of a time step + every scheduled eval
        as ONE device program (dispatches ``_train_iteration_eval_jit``):
        one host->device->host round trip a time step where the per-round
        driver makes one a round and one an eval. For small models the
        per-call latency, not the device, bounds wall-clock, as the
        reference's 0.3 s comm polls did (SURVEY.md §7). The runner enters
        it for a chunkable algorithm (DriftAlgorithm.chunkable) with a
        non-ensemble test path. Trajectories are bitwise-identical to the
        per-round driver's: round r folds the same fold_in(iter_key, r)
        key, and eval matrices are computed on the params right after each
        eval round.

        Argument signatures are tracked per donated-buffer layout: this is
        the donating program (params/opt_states, argnums 1-2), where an
        unnoticed retrace both costs a compile and transiently doubles the
        donated buffers' HBM — exactly the recompile the event stream must
        surface.

        client_masks [R, C] and operands (`StackOperands` with a leading
        [R] axis): the rounds' rows, made on the host before the step
        (ByzantineInjector.schedule; edge ids vary across rounds only after
        a re-home). The two carries stay None: ``byz_stale=True`` makes the
        scan carry every client's previous submission so stale_replay
        attacks replay it (one extra [M, C, ...] buffer in the carry), and
        the delta codec's decoded-diff carry rides the scan when
        ``self.codec == "delta"``.

        Returns (params, opt_states, n [M, C], losses [M, C],
        (corr_tr, loss_tr, corr_te, loss_te) each [E, M, C], total [C])
        where E = len(eval_rounds(R, freq)) and, with ``with_agg_stats``,
        the per-round stats ([R, M, 3] flat, [R, 1 + E, M, 3] hierarchical).

        The rounds run the dense body (M x C pairs) whatever the time
        weights hold. A compact fused round (ROADMAP Reach B2) is a static K
        handed from here to `_iteration_body`'s `_round_body` call, chosen
        as `_round_program` chooses ``train_round``'s.
        """
        args = (params, opt_states, iter_key, x, y, time_w, sample_w,
                feat_mask, lr_scale, R, freq, t, client_masks, operands)
        kwargs = {"byz_stale": byz_stale}
        with self._tracked(
                "train_iteration_eval",
                type(self)._train_iteration_eval_jit, args, kwargs,
                sig=(params, opt_states, x, y, time_w, sample_w, feat_mask,
                     client_masks, operands),
                static=(R, freq, byz_stale)):
            out = self._train_iteration_eval_jit(*args, **kwargs)
        return out if with_agg_stats else out[:6]

    @partial(jax.jit, static_argnums=(0, 10, 11), donate_argnums=(1, 2),
             static_argnames=("byz_stale",))
    def _train_iteration_eval_jit(self, params, opt_states, iter_key, x, y,
                                  time_w, sample_w, feat_mask, lr_scale,
                                  R: int, freq: int, t, client_masks=None,
                                  operands=StackOperands(), *,
                                  byz_stale: bool = False):
        """The program of ``train_iteration_eval``, documented there."""
        return self._iteration_body(
            params, opt_states, iter_key, x, y, time_w, sample_w, feat_mask,
            lr_scale, R, freq, t, client_masks, operands,
            byz_stale=byz_stale)

    def _iteration_body(self, params, opt_states, iter_key, x, y, time_w,
                        sample_w, feat_mask, lr_scale, R: int, freq: int, t,
                        client_masks=None, operands=StackOperands(), *,
                        byz_stale: bool = False):
        """Untraced body of ``_train_iteration_eval_jit``, shared with the
        multi-iteration ``_train_megastep_jit`` outer scan — extracting it
        (instead of nesting jits) keeps the K=1 path's XLA program
        bit-for-bit what it was."""
        evs = self.eval_rounds(R, freq)
        E = len(evs)
        # slot(r): r//freq for the regular cadence; the final round takes the
        # last slot (it may coincide with a regular slot when R-1 % freq == 0,
        # in which case it IS that slot and E == R//freq rounded up).
        xt = jnp.take(x, t, axis=1)
        yt = jnp.take(y, t, axis=1)
        xe = jnp.take(x, t + 1, axis=1)
        ye = jnp.take(y, t + 1, axis=1)
        M = time_w.shape[0]
        C = x.shape[0]
        # loss buffers at eval_dtype (correct-counts stay int32): under a
        # bf16 eval policy the [E, M, C] scan carries halve; under f32
        # (default) these are exactly the historical buffers
        ev_dt = self.precision.eval_jnp
        zero_mats = (jnp.zeros((M, C), jnp.int32), jnp.zeros((M, C), ev_dt),
                     jnp.zeros((M, C), jnp.int32), jnp.zeros((M, C), ev_dt))

        def one(carry, rx):
            r, cm, ops = rx
            # the two carried operands: None (no leaf) where not kept
            p, o, bufs, stale, cprev = carry
            key = jax.random.fold_in(iter_key, r)
            p, o, cp, n, losses, agg_stats, cprev = self._round_body(
                p, o, key, x, y, time_w, sample_w, feat_mask, lr_scale, cm,
                ops._replace(stale_params=stale, codec_prev=cprev))

            is_eval = ((r % freq) == 0) | (r == R - 1)
            slot = jnp.where(r == R - 1, E - 1, r // freq)

            def do_eval(_):
                ctr, ltr, _tot = self._acc_matrix_body(p, xt, yt, feat_mask)
                cte, lte, _ = self._acc_matrix_body(p, xe, ye, feat_mask)
                return (ctr, cast_floating(ltr, ev_dt),
                        cte, cast_floating(lte, ev_dt))

            mats = jax.lax.cond(is_eval, do_eval, lambda _: zero_mats, None)
            bufs = tuple(
                jnp.where(is_eval,
                          jax.lax.dynamic_update_index_in_dim(b, m, slot, 0),
                          b)
                for b, m in zip(bufs, mats))
            return ((p, o, bufs, cp if byz_stale else None, cprev),
                    (n, losses, agg_stats))

        bufs0 = tuple(jnp.zeros((E, M, C), d) for d in
                      (jnp.int32, ev_dt, jnp.int32, ev_dt))
        stale0 = cprev0 = None
        if byz_stale:
            # round 0's stale replay degenerates to "re-send the broadcast
            # params" (a zero update) — there is no earlier submission
            stale0 = jax.tree_util.tree_map(
                lambda l: jnp.broadcast_to(
                    l[:, None], (l.shape[0], C, *l.shape[1:])), params)
        if self.codec == "delta":
            # round 0 deltas against the broadcast params (zero history)
            cprev0 = jax.tree_util.tree_map(
                lambda l: jnp.zeros((l.shape[0], C, *l.shape[1:]), l.dtype),
                params)
        (params, opt_states, bufs, _, _), (ns, ls, stats) = jax.lax.scan(
            one, (params, opt_states, bufs0, stale0, cprev0),
            (jnp.arange(R, dtype=jnp.int32), client_masks, operands))
        total = jnp.full((C,), x.shape[2] * math.prod(y.shape[3:]),
                         dtype=jnp.int32)
        return params, opt_states, ns[-1], ls[-1], bufs, total, stats

    # ------------------------------------------------------------------
    def train_megastep(self, params, base_key, x, y, time_ws, sample_w,
                       feat_mask, lr_scale, t0, R: int, freq: int, K: int,
                       client_masks=None, operands=StackOperands(),
                       x_steps=None, y_steps=None, *,
                       byz_stale: bool = False):
        """K whole time steps (each an R-round fused scan with scheduled
        evals) as ONE device program (dispatches ``_train_megastep_jit``).

        time_ws: [K, M, C, T1] — the per-step time weights the algorithm
        decided host-side BEFORE the block (the megastep contract: no drift
        decision may depend on results inside the block, which is what
        ``DriftAlgorithm.megastep_horizon`` certifies). client_masks
        [K, R, C] or None and operands (`StackOperands` with leading
        [K, R] axes, a field None when the feature is off) are the per-step
        fault / hierarchy schedules — each step's row feeds
        ``_iteration_body`` exactly as the K=1 fused path would.
        Population cohorts pass ``x=y=None`` and the stacked per-step
        gathers as ``x_steps/y_steps`` [K, C, T1, N, ...] instead — the
        scan re-binds each step's cohort shard the way the host re-binds
        ``self.x`` between iterations. t0 is a traced operand — advancing
        the block start never retraces.

        Returns stacked per-step results ``(ps [K, M, ...], ns [K, M, C],
        losses [K, M, C], bufs (4x [K, E, M, C]), total [C],
        agg_stats [K, R, M, 3])``; step j of the block is bitwise-identical
        to a K=1 dispatch at t0+j because the scan folds the same
        ``iteration_key(base_key, t0+j)`` and re-inits the optimizer states
        (and the stale-replay / delta-codec carries) from the same
        value-independent seeds. Like ``train_iteration_eval``, whose body
        it scans, every round runs the dense body.
        """
        args = (params, base_key, x, y, time_ws, sample_w, feat_mask,
                lr_scale, t0, R, freq, K, client_masks, operands, x_steps,
                y_steps)
        kwargs = {"byz_stale": byz_stale}
        with self._tracked(
                "train_megastep", type(self)._train_megastep_jit, args,
                kwargs,
                sig=(params, x, y, time_ws, sample_w, feat_mask,
                     client_masks, operands, x_steps, y_steps),
                static=(R, freq, K, byz_stale)):
            return self._train_megastep_jit(*args, **kwargs)

    # NOTE: no buffer donation here — every output is K-stacked, so the
    # [M, ...] params input can never alias an output buffer (XLA would
    # warn "donated buffers were not usable" on every compile).
    @partial(jax.jit, static_argnums=(0, 10, 11, 12),
             static_argnames=("byz_stale",))
    def _train_megastep_jit(self, params, base_key, x, y, time_ws, sample_w,
                            feat_mask, lr_scale, t0, R: int, freq: int,
                            K: int, client_masks=None,
                            operands=StackOperands(), x_steps=None,
                            y_steps=None, *, byz_stale: bool = False):
        """Outer scan over K time steps, each one `_iteration_body` call.

        The host round-trip this kills: the K=1 driver fetches params,
        re-derives the iteration key, re-inits optimizer states and
        re-dispatches per step. Here the key derivation
        (``iteration_key(base_key, t0+k)`` — a pure fold_in chain, traceable
        and bitwise-equal to the host-side derivation) and the opt-state
        re-init (value-independent zeros) move inside the scan, and the
        data-slice index ``t0 + k`` advances as a traced value, so the host
        touches the device once per K steps. Per-step end params ride the
        stacked output — they are [M, ...] (no client axis), cheap, and the
        driver needs them for after_round replay and divergence rollback.

        With a 2-D ``(models, clients)`` mesh on ``self.mesh``, the carry
        params, in-scan opt states and time-weight slices are annotated
        with `constrain_pool` so GSPMD shards the [M, C, ...] stacks over
        both axes instead of replicating M; on a 1-D or single-device mesh
        the constraints degrade to replication no-ops.
        """
        M = time_ws.shape[1]
        C = x.shape[0] if x is not None else x_steps.shape[1]

        def one_step(p, xs):
            k, tw_k, cm_k, ops_k, x_k, y_k = xs
            # population mode: each step trains on ITS cohort's gathered
            # shard; the time index inside the shard is still t (gathers
            # keep the full [T1] axis, only the client axis is re-drawn)
            xx = x if x is not None else x_k
            yy = y if y is not None else y_k
            t = t0 + k
            it_key = iteration_key(base_key, t)
            o0 = self.init_opt_states(p, M, C)
            o0 = constrain_pool(self.mesh, o0, model_axis=0, client_axis=1)
            tw_k = constrain_pool(self.mesh, tw_k, model_axis=0,
                                  client_axis=1)
            # stale-replay buffers and the delta-codec carry re-seed INSIDE
            # _iteration_body per scanned step — the same per-iteration
            # reset the host driver performs (_byz_stale/_codec_prev = None)
            p, _o, n, losses, bufs, total, stats = self._iteration_body(
                p, o0, it_key, xx, yy, tw_k, sample_w, feat_mask, lr_scale,
                R, freq, t, cm_k, ops_k, byz_stale=byz_stale)
            p = constrain_pool(self.mesh, p, model_axis=0)
            return p, (p, n, losses, bufs, total, stats)

        params = constrain_pool(self.mesh, params, model_axis=0)
        _, (ps, ns, ls, bufs, tots, stats) = jax.lax.scan(
            one_step, params,
            (jnp.arange(K, dtype=jnp.int32), time_ws, client_masks, operands,
             x_steps, y_steps))
        # eval totals are a pure function of (x, feat_mask) — constant over
        # the block, so return one step's [C] row, same shape as K=1
        return ps, ns, ls, bufs, tots[0], stats

    # ------------------------------------------------------------------
    def acc_matrix(self, params, x, y, feat_mask):
        """Batched [M, C] eval of every model on every client's data.

        Replaces the reference's hottest loop — M x C sequential full-dataset
        inferences with CPU<->GPU shuttling (train_acc_matrix,
        FedAvgEnsDataLoader.py:1074-1085) — with one [M, C, N] forward.
        x: [C, N, ...]; returns (correct [M, C], loss_sum [M, C], total [C]).
        """
        args = (params, x, y, feat_mask)
        with self._tracked("acc_matrix", type(self)._acc_matrix_jit, args,
                           sig=args):
            return self._acc_matrix_jit(*args)

    @partial(jax.jit, static_argnums=0)
    def _acc_matrix_jit(self, params, x, y, feat_mask):
        return self._acc_matrix_body(params, x, y, feat_mask)

    def _acc_matrix_body(self, params, x, y, feat_mask):
        """Hits and summed loss count labels: one per sample, or one per
        token where ``y`` is [C, N, L], or those the model's
        ``eval_counts`` counts; ``total`` is a client's labels."""
        def one(p_m, f_m):
            def per_client(xc, yc):
                xin = xc * f_m if xc.dtype != jnp.int32 else xc
                logits = self.apply_fn(p_m, xin)
                if self.eval_counts is not None:
                    return self.eval_counts(logits, yc)
                logp = jax.nn.log_softmax(logits)
                nll = -jnp.take_along_axis(logp, yc[..., None], axis=-1).sum()
                return (logits.argmax(-1) == yc).sum(), nll
            if self.client_axis == "scan":
                # the clients in turn, and a client's samples in chunks of
                # the training batch: one forward at a time, of the size the
                # local steps run, whatever M, C and N are
                N = x.shape[1]
                B = min(self.batch_size, N)
                B = B if N % B == 0 else N

                def chunked(xc_yc):
                    xc, yc = (a.reshape((N // B, B) + a.shape[1:])
                              for a in xc_yc)
                    counts = jax.lax.map(lambda ab: per_client(*ab),
                                         (xc, yc))
                    return tuple(a.sum() for a in counts)
                return jax.lax.map(chunked, (x, y))
            return jax.vmap(per_client)(x, y)
        if self.client_axis == "scan":
            counts = jax.lax.map(lambda pf: one(*pf), (params, feat_mask))
        else:
            counts = jax.vmap(one)(params, feat_mask)
        if self.eval_counts is not None:
            # a client's labels, the same under every model
            correct, loss_sum, labels = counts
            return correct, loss_sum, labels[0]
        correct, loss_sum = counts
        labels = x.shape[1] * math.prod(y.shape[2:])
        total = jnp.full((x.shape[0],), labels, dtype=jnp.int32)
        return correct, loss_sum, total

    # ------------------------------------------------------------------
    @partial(jax.jit, static_argnums=(0, 5))
    def ensemble_eval(self, params, x, y, ens_weights, mode: str = "hard",
                      model_mask=None, feat_mask=None):
        """Weighted-vote ensemble accuracy per client.

        mode='hard': AUE — each model casts its weight on its argmax class
        (FedAvgEnsAggregatorAue.py:256-283).
        mode='soft': KUE — kappa-weighted softmax sum over models with
        kappa > 0, worst model excluded (FedAvgEnsAggregatorKue.py:234-262).
        x: [C, N, ...]; ens_weights: [M] or [M, C] (AUE-PC per-client weights,
        FedAvgEnsAggregatorAuePc.py:260). Returns (correct [C], total [C]).
        """
        M = jax.tree_util.tree_leaves(params)[0].shape[0]
        if model_mask is None:
            model_mask = jnp.ones((M,), dtype=jnp.float32)
        if ens_weights.ndim == 1:
            ens_weights = jnp.broadcast_to(ens_weights[:, None],
                                           (M, x.shape[0]))

        def one_model(p_m, f_m):
            def per_client(xc):
                xin = xc * f_m if xc.dtype != jnp.int32 else xc
                return self.apply_fn(p_m, xin)          # [N, K]
            return jax.vmap(per_client)(x)              # [C, N, K]
        if feat_mask is None:
            feat_mask = jnp.ones((M,) + (1,) * (x.ndim - 2), dtype=x.dtype) \
                if x.dtype != jnp.int32 else jnp.ones((M, 1), dtype=jnp.float32)
        logits = jax.vmap(one_model)(params, feat_mask)  # [M, C, N, K]

        w = ens_weights * model_mask[:, None]            # [M, C]
        if mode == "hard":
            votes = jax.nn.one_hot(logits.argmax(-1), logits.shape[-1])
        else:
            votes = jax.nn.softmax(logits, axis=-1)
            w = jnp.maximum(w, 0.0) * (ens_weights > 0)  # kappa>0 gate
        combined = (votes * w[:, :, None, None]).sum(axis=0)   # [C, N, K]
        correct = (combined.argmax(-1) == y).sum(axis=1)
        # Ensemble NLL from the normalised vote distribution, so Test/Loss
        # stays a real series for AUE/KUE runs.
        probs = combined / jnp.maximum(combined.sum(-1, keepdims=True), 1e-12)
        nll = -jnp.log(jnp.take_along_axis(probs, y[..., None], -1)[..., 0] + 1e-12)
        loss_sum = nll.sum(axis=1)
        total = jnp.full((x.shape[0],), x.shape[1], dtype=jnp.int32)
        return correct, total, loss_sum

    # ------------------------------------------------------------------
    def acc_cells(self, params, x, y, feat_mask):
        """Tracked dispatch of ``_acc_cells_jit`` (see there)."""
        args = (params, x, y, feat_mask)
        with self._tracked("acc_cells", type(self)._acc_cells_jit, args,
                           sig=args):
            return self._acc_cells_jit(*args)

    @partial(jax.jit, static_argnums=0)
    def _acc_cells_jit(self, params, x, y, feat_mask):
        """Correct-prediction counts per (model, client, time step).

        x: [C, T1, N, ...] -> correct [M, C, T1]. Powers FedDrift's
        cluster-accuracy matrix (reference _infer_subset over concatenated
        per-cluster datasets, FedAvgEnsDataLoader.py:899-931) exactly:
        cluster_acc[i][j] = sum over cells assigned to cluster j of
        correct[i, c, t] / volume — full data, not the reference's 20-batch
        subsample. lax.map over the time axis bounds activation memory for
        large models.
        """
        def at_time(xt_yt):
            xt, yt = xt_yt                               # [C, N, ...], [C, N]
            def one(p_m, f_m):
                def per_client(xc, yc):
                    xin = xc * f_m if xc.dtype != jnp.int32 else xc
                    logits = self.apply_fn(p_m, xin)
                    return (logits.argmax(-1) == yc).sum()
                return jax.vmap(per_client)(xt, yt)
            return jax.vmap(one)(params, feat_mask)      # [M, C]
        x_t = jnp.moveaxis(x, 1, 0)                      # [T1, C, N, ...]
        y_t = jnp.moveaxis(y, 1, 0)
        correct = jax.lax.map(at_time, (x_t, y_t))       # [T1, M, C]
        return jnp.moveaxis(correct, 0, 2)               # [M, C, T1]

    # ------------------------------------------------------------------
    @partial(jax.jit, static_argnums=0)
    def mse_matrix(self, params, x, y, feat_mask):
        """Per-(model, client) Brier sums ``sum_n (1 - p_y(x_n))^2``.

        Powers the AUE ensemble-weight formula ``1/(MSEr + MSEi + eps)``
        (FedAvgEnsAggregatorAue.py:55-87, _mse at :219-234). x: [C, N, ...]
        -> (mse_sum [M, C], total [C]).
        """
        def one(p_m, f_m):
            def per_client(xc, yc):
                xin = xc * f_m if xc.dtype != jnp.int32 else xc
                probs = jax.nn.softmax(self.apply_fn(p_m, xin), axis=-1)
                p_true = jnp.take_along_axis(probs, yc[:, None], axis=-1)[:, 0]
                return ((1.0 - p_true) ** 2).sum()
            return jax.vmap(per_client)(x, y)
        mse_sum = jax.vmap(one)(params, feat_mask)
        total = jnp.full((x.shape[0],), x.shape[1], dtype=jnp.int32)
        return mse_sum, total

    # ------------------------------------------------------------------
    @partial(jax.jit, static_argnums=0)
    def confusion_matrices(self, params, x, y, feat_mask):
        """Per-(model, client) confusion matrices [M, C, K, K] (KUE kappa)."""
        if y.ndim > 2:
            raise ValueError(
                "confusion_matrices (KUE's kappa) reads one label a sample; "
                f"y has trailing label axes {y.shape[2:]} (a label per token)")
        K = self.num_classes
        def one(p_m, f_m):
            def per_client(xc, yc):
                xin = xc * f_m if xc.dtype != jnp.int32 else xc
                return confusion_matrix(self.apply_fn(p_m, xin), yc, K)
            return jax.vmap(per_client)(x, y)
        return jax.vmap(one)(params, feat_mask)


# ----------------------------------------------------------------------
@dataclass(eq=False)
class ForwardStep:
    """Forward-only serving program over the [M, ...] model pool.

    The read-path counterpart of TrainStep: ONE compiled program answers a
    whole micro-batch of inference requests that may target DIFFERENT
    cluster models. Inputs are a padded request batch ``x [B, ...]`` plus a
    per-row model index ``model_idx [B]``; the program gathers each row's
    param slice out of the pool and vmaps the module apply, so a
    mixed-cluster batch costs one dispatch instead of B.

    Shares TrainStep's compile-count detector: B is expected to come from a
    small static bucket set (platform/serving.py), so after warm-up every
    steady-state dispatch hits an already-seen signature —
    ``jit_recompiles{fn=serve_forward}`` staying at 0 is the SERVE bench /
    regress gate.
    """

    apply_fn: Callable          # (params, x) -> logits
    # Optional 2-D (models, clients) mesh: the pool's [M] axis is annotated
    # with constrain_pool so GSPMD keeps the PR 10 layout; None / 1-device
    # meshes leave the program untouched (no committed-sharding recompile).
    mesh: object = field(default=None, repr=False)
    cost_capture: str = "lowered"
    _signatures: dict = field(default_factory=dict, repr=False)

    # the detector + cost harvest are TrainStep's, verbatim: one
    # implementation, one event vocabulary (jit_compile/jit_recompile)
    _note_signature = TrainStep._note_signature
    _capture_cost = TrainStep._capture_cost
    _tracked = TrainStep._tracked

    def forward(self, params, x, model_idx):
        """Tracked dispatch: logits [B, K] for x [B, ...] routed by
        model_idx [B] into params [M, ...].

        Each bucket size is tracked as its OWN program
        (``serve_forward_b<B>``): warming N buckets is N jit_compiles and
        zero jit_recompiles, so any nonzero ``jit_recompiles{fn=
        serve_forward_b*}`` is a genuine steady-state anomaly (a new
        dtype/sharding/committed-ness), not bucket-ladder noise.
        """
        fn = f"serve_forward_b{x.shape[0]}"
        args = (params, x, model_idx)
        with self._tracked(fn, type(self)._forward_jit, args, sig=args):
            return self._forward_jit(*args)

    @partial(jax.jit, static_argnums=0)
    def _forward_jit(self, params, x, model_idx):
        params = constrain_pool(self.mesh, params, model_axis=0)
        rows = jax.tree_util.tree_map(lambda p: p[model_idx], params)

        def one(p_r, x_r):
            # [1, ...] -> [1, K]: the batched apply the eval programs use. On
            # the CPU a bucket of 2+ is bitwise pool.apply, of 1 within an ulp
            return self.apply_fn(p_r, x_r[None])[0]
        return jax.vmap(one)(rows, x)
