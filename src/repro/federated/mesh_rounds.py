"""Mesh-level DEFL round step: the datacenter realization of Algorithm 1.

Clients are a stacked leading axis C on every param/opt leaf, sharded over
the mesh's client axes ('data', and 'pod' x 'data' multi-pod). One round
step = V local SGD steps per client (vmapped: zero cross-client
collectives) + weighted FedAvg aggregation (one param-sized all-reduce) +
broadcast. The paper's talk/work ratio is therefore visible directly in
the compiled HLO: collective bytes per round ~ |params|, compute ~ V
forward/backward passes (see EXPERIMENTS.md §Roofline).

Aggregation modes:
  'allreduce'  : psum-style weighted mean in fp32 (paper-faithful sync).
  'int8_gather': beyond-paper — per-client int8 quantized deltas are
                 all-gathered and combined locally, shrinking collective
                 bytes ~4x (federated/compression.py semantics inline).
  'int8_stochastic': the exact federated/compression.py quantizer
                 (stochastic rounding, one fp32 scale per 1024-chunk) run
                 in-graph on per-client deltas — the compiled form of the
                 simulator's host-side compress/decompress roundtrip.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.optim.api import Optimizer, apply_updates


def local_steps_fn(loss_fn: Callable, opt: Optimizer):
    """(params, opt_state, batches[V]) -> (params', opt_state', mean_loss).

    The V-step loss mean accumulates in the scan CARRY (a sequential
    left-fold) rather than stacking and reducing: the fold's partial sums
    are prefix-stable, so the envelope form below — the same fold over
    V_env steps whose padded tail adds exact zeros — reproduces it bit for
    bit at any padding (XLA's reduce would re-associate with length)."""

    def run(params, opt_state, batches):
        def step(carry, batch):
            p, s, acc = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
            updates, s = opt.update(grads, s, p)
            return (apply_updates(p, updates), s, acc + loss), None

        (params, opt_state, total), _ = jax.lax.scan(
            step, (params, opt_state, jnp.zeros(())), batches)
        V = jax.tree.leaves(batches)[0].shape[0]
        return params, opt_state, total / V

    return run


def envelope_local_steps_fn(loss_fn: Callable, opt: Optimizer):
    """`local_steps_fn` over a padded (V_env, B_env) shape envelope.

    The Study API (federated/study.py) runs arms with different (b, V)
    plans in ONE vmapped fleet by padding every member to the group's
    common envelope; this is the member-level local step that makes the
    padding a bitwise no-op:

      batches      (V_env, B_env, ...) — the member's real V x b draws,
                   padded along both axes
      v_mask       (V_env,) 0/1 — 1 for the member's own local steps;
                   padded steps run (shapes are static) but their
                   params/opt writes are masked out with `where`, exactly
                   the ragged-final-chunk `valid` trick of
                   build_round_chunk, so they cannot perturb state
      sample_mask  (B_env,) 0/1 and n_samples (f32 count) — forwarded to
                   the masked loss; loss_fn(params, batch, sample_mask, n)
                   must make padded samples exact zeros in the loss and
                   its gradient (e.g. models.cnn.cnn_loss_masked, whose
                   conv backward is pad-stable via `_ps_matmul` or
                   `_ps_conv`)

    The returned mean loss accumulates in the scan carry exactly like
    `local_steps_fn`'s (padded steps add an exact 0) and divides by the
    member's own V — bit-identical to the unpadded fold."""

    def run(params, opt_state, batches, v_mask, sample_mask, n_samples):
        def step(carry, xs):
            p, s, acc = carry
            batch, valid = xs
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                p, batch, sample_mask, n_samples)
            updates, s2 = opt.update(grads, s, p)
            p2 = apply_updates(p, updates)
            keep = lambda nw, old: jnp.where(valid > 0, nw, old.astype(nw.dtype))  # noqa: E731
            return ((jax.tree.map(keep, p2, p), jax.tree.map(keep, s2, s),
                     acc + jnp.where(valid > 0, loss, 0.0)), None)

        (params, opt_state, total), _ = jax.lax.scan(
            step, (params, opt_state, jnp.zeros(())), (batches, v_mask))
        return params, opt_state, total / jnp.sum(v_mask)

    return run


def _participation_weights(weights, mask):
    """FedAvg weights renormalized over the round's participating clients.

    mask is a traced (C,) array (1.0 = update arrived). Dropped clients get
    exactly-zero weight (their rows are also reset to finite pre-round
    values before the contraction, so x * 0.0 contributes an exact +0.0
    and a masked client can never perturb the aggregate bits). A zero-
    participation round divides by 1 instead of 0; the caller keeps the old
    params via `_keep_old_params`. Returns (weights', any_participant)."""
    wm = weights.astype(jnp.float32) * mask.astype(jnp.float32)
    s = jnp.sum(wm)
    any_p = s > 0
    return wm / jnp.where(any_p, s, 1.0), any_p


def _keep_old_params(agg_p, old_params, any_p):
    """Zero-participation guard: no update arrived -> params unchanged."""
    return jax.tree.map(
        lambda a, o: jnp.where(any_p, a, o.astype(a.dtype)), agg_p, old_params)


def _select_participating_state(new_s, old_s, mask):
    """Per-client opt-state select: dropped clients keep their pre-round
    state (the loop backend never runs them, so momentum etc. must not
    advance). mask broadcasts from (C,) over each leaf's trailing dims."""
    def sel(n, o):
        m = mask.reshape((mask.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m > 0, n, o)

    return jax.tree.map(sel, new_s, old_s)


def _masked_clock(t_cp, t_cm, clock_mask, V):
    """Eq. 8 round clock as the straggler max over *participating* clients,
    computed in-graph from traced per-client delay inputs (seconds).

    Zero participation falls back to the full-population max: the
    synchronous server's wait times out at the slowest possible client, so
    the wall clock advances even though no update arrives (host twin:
    core.delay.masked_round_times)."""
    any_p = jnp.any(clock_mask > 0)

    def mmax(t):
        t = t.astype(jnp.float32)
        masked = jnp.max(jnp.where(clock_mask > 0, t, -jnp.inf))
        return jnp.where(any_p, masked, jnp.max(t))

    T_cm, T_cp = mmax(t_cm), mmax(t_cp)
    return {"T_cm": T_cm, "T_cp": T_cp, "T_round": T_cm + V * T_cp}


def _weighted_client_sum(weights, x):
    """sum_c w_c x_c over the leading client axis, as an explicit
    multiply + reduce rather than a tensordot/dot_general contraction.

    Deliberate: XLA lowers a dot_general differently once an extra
    leading batch dimension appears (the fleet vmap in
    `build_fleet_chunk`), reassociating the fp32 accumulation and
    breaking bit-identity between a vmapped fleet member and the same
    seed run alone. A reduce keeps the per-output-element accumulation
    order over C fixed regardless of leading batch dims, which is what
    the run_fleet == sequential-run bit-parity contract rests on."""
    w = weights.astype(jnp.float32).reshape(
        (weights.shape[0],) + (1,) * (x.ndim - 1))
    return jnp.sum(w * x.astype(jnp.float32), axis=0)


def _weighted_mean_bcast(stacked, weights):
    """sum_c w_c x_c, broadcast back to all C rows (keeps leaves (C, ...))."""

    def agg(x):
        mean = _weighted_client_sum(weights, x)
        return jnp.broadcast_to(mean[None].astype(x.dtype), x.shape)

    return jax.tree.map(agg, stacked)


def _int8_gather_mean_bcast(new_params, old_params, weights, key):
    """Quantize per-client deltas to int8, combine, add to the (shared) old
    params, broadcast. old_params rows are identical pre-round, so using row
    data is consistent under the client-axis sharding."""

    def agg(new, old):
        delta = (new - old).astype(jnp.float32)  # (C, ...)
        flat = delta.reshape(delta.shape[0], -1)
        absmax = jnp.max(jnp.abs(flat), axis=-1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
        # The all-gather happens here under GSPMD: q is client-sharded and the
        # weighted sum contracts the client axis.
        deq = q.astype(jnp.float32) * scale
        mean = jnp.tensordot(weights.astype(jnp.float32), deq, axes=(0, 0))
        agg_new = old[0].reshape(-1) + mean
        return jnp.broadcast_to(
            agg_new.reshape(old.shape[1:])[None].astype(new.dtype), new.shape)

    return jax.tree.map(agg, new_params, old_params)


def _int8_stochastic_mean_bcast(new_params, old_params, weights, keys, impl):
    """federated/compression.py semantics in-graph: every client's delta
    goes through the stochastic int8 quantize/dequantize roundtrip (per-
    1024-chunk fp32 scales), then weighted FedAvg + broadcast. keys (C, 2)
    carry one PRNG key per client; fed from the same sequential schedule as
    the host loop, the reconstruction is bit-identical to it."""
    from repro.federated import compression

    deltas = jax.tree.map(lambda n, o: n - o, new_params, old_params)
    rec = jax.vmap(
        lambda d, k: compression.decompress_update(
            compression.compress_update(d, k, impl=impl), impl=impl)
    )(deltas, keys)

    def agg(r, old):
        flat = r.reshape(r.shape[0], -1).astype(jnp.float32)
        # multiply+reduce, not tensordot: see _weighted_client_sum.
        mean = _weighted_client_sum(weights, flat)
        out = old[0].reshape(-1).astype(jnp.float32) + mean
        return jnp.broadcast_to(
            out.reshape(old.shape[1:])[None].astype(old.dtype), old.shape)

    return jax.tree.map(agg, rec, old_params)


def _int8_shardmap_sync(mesh, param_specs_tree, client_axes):
    """Explicit-collective int8 sync: each client quantizes its delta to
    int8 locally, `lax.all_gather` moves INT8 (+ fp32 scales) over the
    client axes, dequant + weighted-combine happen after the gather.

    Why not GSPMD: quantize-then-contract under pjit lets the partitioner
    place the collective on the dequantized fp32 tensor (measured: WORSE
    than plain all-reduce — EXPERIMENTS.md §Perf iteration A3/B-int8).
    shard_map pins int8 on the wire: ~4x fewer sync bytes than fp32
    all-reduce at one extra rounding step (unbiased via the stochastic
    quantizer semantics; deterministic rounding here since the round-step
    PRNG lives outside the sync)."""
    axis = client_axes if len(client_axes) > 1 else client_axes[0]

    def sync(new_p, old_p, weights):
        def leaf(new, old, spec):
            def body(n_loc, o_loc, w_all):
                # n_loc/o_loc: (1, ...) local client row(s).
                delta = (n_loc - o_loc).astype(jnp.float32).reshape(
                    n_loc.shape[0], -1)
                absmax = jnp.max(jnp.abs(delta), axis=-1, keepdims=True)
                scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
                q = jnp.clip(jnp.round(delta / scale), -127, 127).astype(jnp.int8)
                qg = jax.lax.all_gather(q, axis)  # int8 on the wire
                sg = jax.lax.all_gather(scale, axis)
                if isinstance(axis, tuple):
                    qg = qg.reshape(-1, *qg.shape[len(axis):])
                    sg = sg.reshape(-1, *sg.shape[len(axis):])
                qg = qg.reshape(-1, delta.shape[-1])
                sg = sg.reshape(-1, 1)
                mean = jnp.tensordot(
                    w_all, qg.astype(jnp.float32) * sg, axes=(0, 0))
                out = o_loc.reshape(o_loc.shape[0], -1) + mean[None]
                return out.reshape(o_loc.shape).astype(n_loc.dtype)

            in_specs = (spec, spec, jax.sharding.PartitionSpec())
            return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=spec, check_vma=False)(
                new, old, weights)

        return jax.tree.map(leaf, new_p, old_p, param_specs_tree)

    return sync


def _psum_shardmap_sync(mesh, param_specs_tree, client_axes):
    """Explicit-collective fp32 FedAvg sync: weighted psum over the client
    axes inside shard_map.

    Why not GSPMD tensordot: for leaves whose trailing dims are replicated
    (e.g. small attention weight stacks) the partitioner lowers the
    client-axis contraction as a FULL all-gather of the stacked fp32
    weights (measured 197 GB/leaf on llava-next-34b — EXPERIMENTS.md
    §Perf B). A pinned psum moves 2x the leaf shard instead."""
    axes = tuple(client_axes)

    def sync(new_p, weights):
        def leaf(new, spec):
            def body(n_loc, w_all):
                idx = jax.lax.axis_index(axes[0])
                if len(axes) > 1:
                    for a in axes[1:]:
                        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
                # n_loc is this shard's (rows, ...) slice of the client
                # axis — rows > 1 when C exceeds the device count. Each
                # shard reduces its own rows locally, then one psum of the
                # param-sized partial crosses the wire.
                rows = n_loc.shape[0]
                w = jax.lax.dynamic_slice_in_dim(
                    w_all, idx * rows, rows).astype(jnp.float32)
                wl = w.reshape((rows,) + (1,) * (n_loc.ndim - 1))
                local = jnp.sum(wl * n_loc.astype(jnp.float32), axis=0,
                                keepdims=True)
                agg = jax.lax.psum(local,
                                   axes if len(axes) > 1 else axes[0])
                return jnp.broadcast_to(agg, n_loc.shape).astype(n_loc.dtype)

            in_specs = (spec, jax.sharding.PartitionSpec())
            return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=spec, check_vma=False)(new, weights)

        return jax.tree.map(leaf, new_p, param_specs_tree)

    return sync


def _guard_clients(guard, new_p, params_C, losses, mask):
    """Divergence-guard sanitation of per-client updates (fault layer).

    guard is a STATIC (max_norm, reject_nonfinite) pair (see
    faults.FaultModel.guard_spec — static per compiled graph, so the
    clipping ops only exist when max_norm is finite). Per client the
    update's global L2 norm across all leaves decides its fate:

      non-finite (norm or loss) + reject  -> masked out of this round's
          aggregation (the caller's mask-handling resets the row to its
          pre-round state, so a NaN client restarts from the next global
          model instead of poisoning it)
      norm > max_norm -> delta scaled back to max_norm before
          aggregation (the opt state keeps the raw step — clipping caps
          the aggregate's exposure, it does not rewrite client history)

    Returns (new_p, mask') where mask' folds the rejections into the
    participation mask (mask=None is treated as full participation).
    """
    max_norm, reject = guard
    deltas = jax.tree.map(
        lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
        new_p, params_C)
    sq = jnp.zeros(losses.shape[0], jnp.float32)
    for d in jax.tree.leaves(deltas):
        sq = sq + jnp.sum(d.reshape(d.shape[0], -1) ** 2, axis=1)
    norm = jnp.sqrt(sq)
    finite = jnp.isfinite(norm) & jnp.isfinite(losses)
    if max_norm < float("inf"):
        scale = jnp.where(
            finite, jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12)),
            1.0)

        def clip(o, d):
            s = scale.reshape((scale.shape[0],) + (1,) * (d.ndim - 1))
            return (o.astype(jnp.float32) + d * s).astype(o.dtype)

        new_p = jax.tree.map(clip, params_C, deltas)
    if reject:
        ok = finite.astype(jnp.float32)
        mask = ok if mask is None else mask * ok
    return new_p, mask


def build_round_step(
    loss_fn: Callable,
    opt: Optimizer,
    V: int,
    aggregation: str = "allreduce",
    mesh=None,
    param_specs_tree=None,
    client_axes=None,
    impl: str = "xla",
    envelope: bool = False,
    guard=None,
):
    """Build round_step(params_C, opt_C, batches, weights, keys=None,
    mask=None, clock_mask=None, t_cp=None, t_cm=None, env=None) with
    leaves stacked on a leading client axis C and batches (C, V, ...).

    aggregation in ('allreduce_shardmap', 'int8_shardmap') needs
    (mesh, param_specs_tree, client_axes) for the explicit-collective path;
    'allreduce' is the plain GSPMD tensordot used on a single device.
    'int8_stochastic' additionally takes keys (C, 2) — one quantizer PRNG
    key per client — and honors impl ('xla' | 'pallas') for the quantize
    kernel. metrics carries both the weighted loss and the raw per-client
    losses so callers can match the host loop's unweighted mean.

    Scenario inputs (all traced (C,) arrays — per-round values change
    without retracing, and nothing here forces a host sync):
      mask        participation mask; weights are renormalized over the
                  participating clients (`_participation_weights`) and
                  dropped clients keep their pre-round opt state. With no
                  participants at all, params pass through unchanged.
                  mask=None is the legacy full-participation path and is
                  bit-identical to it (mask of ones multiplies weights by
                  exactly 1.0 and the zero-guard selects are no-ops).
      clock_mask  clients the synchronous server waits for (defaults to
                  mask); with t_cp/t_cm (per-client seconds, Eqs. 4/6)
                  metrics gains the in-graph Eq. 8 round clock
                  ('T_cm', 'T_cp', 'T_round') as the straggler max over
                  waiting clients.

    envelope=True runs the (V, b) shape-envelope form: `loss_fn` takes
    (params, batch, sample_mask, n) and batches are (C, V_env, B_env, ...)
    with the per-member masks arriving via `env` — a dict of traced
    arrays {'v_mask' (V_env,), 'sample_mask' (B_env,), 'n_samples' f32,
    'v_count' f32} shared across the C clients of one member (the Study
    API's members all pad client-uniformly). The in-graph T_round then
    uses the traced v_count in place of the static V.

    guard (static (max_norm, reject_nonfinite) pair, or None) compiles
    the fault layer's divergence sanitation in front of aggregation: see
    `_guard_clients`. Rejections fold into the participation mask, so
    downstream weight renormalization / state selection / clock handling
    are untouched; metrics gains 'mask_eff' (the post-guard mask) so
    chunk-level consumers count participants guard-aware. guard=None
    builds today's graph unchanged.
    """
    local = (envelope_local_steps_fn(loss_fn, opt) if envelope
             else local_steps_fn(loss_fn, opt))
    int8_sync = psum_sync = None
    if aggregation == "int8_shardmap":
        int8_sync = _int8_shardmap_sync(mesh, param_specs_tree, client_axes)
    if aggregation == "allreduce_shardmap":
        psum_sync = _psum_shardmap_sync(mesh, param_specs_tree, client_axes)

    def round_step(params_C, opt_C, batches, weights, keys=None,
                   mask=None, clock_mask=None, t_cp=None, t_cm=None,
                   env=None):
        # Named scopes (metadata only) split the compiled round into the
        # paper's work (local_steps) and talk (aggregate) in a profile.
        with jax.named_scope("local_steps"):
            if envelope:
                new_p, new_s, losses = jax.vmap(
                    local, in_axes=(0, 0, 0, None, None, None))(
                        params_C, opt_C, batches, env["v_mask"],
                        env["sample_mask"], env["n_samples"])
            else:
                new_p, new_s, losses = jax.vmap(local)(params_C, opt_C,
                                                       batches)
        if guard is not None:
            new_p, mask = _guard_clients(guard, new_p, params_C, losses, mask)
        any_p = None
        if mask is not None:
            weights, any_p = _participation_weights(weights, mask)
            # Replace dropped clients' rows with their pre-round state (and
            # zero their loss) BEFORE the contraction: weight-0 alone is
            # not enough if a never-aggregated client diverged to inf/NaN
            # (0 * inf = NaN would poison the weighted mean, which the
            # loop backend — never running that client — cannot hit).
            new_p = _select_participating_state(new_p, params_C, mask)
            new_s = _select_participating_state(new_s, opt_C, mask)
            losses = jnp.where(mask > 0, losses, 0.0)
        with jax.named_scope("aggregate"):
            if aggregation == "allreduce":
                agg_p = _weighted_mean_bcast(new_p, weights)
            elif aggregation == "allreduce_shardmap":
                agg_p = psum_sync(new_p, weights)
            elif aggregation == "int8_gather":
                agg_p = _int8_gather_mean_bcast(
                    new_p, params_C, weights, key=None)
            elif aggregation == "int8_stochastic":
                assert keys is not None, (
                    "int8_stochastic needs per-client keys")
                agg_p = _int8_stochastic_mean_bcast(
                    new_p, params_C, weights, keys, impl)
            elif aggregation == "int8_shardmap":
                agg_p = int8_sync(new_p, params_C, weights)
            else:
                raise ValueError(aggregation)
            if any_p is not None:
                agg_p = _keep_old_params(agg_p, params_C, any_p)
        metrics = {"loss": jnp.tensordot(weights.astype(jnp.float32),
                                         losses, axes=(0, 0)),
                   "per_client_loss": losses}
        if mask is not None:
            metrics["n_participants"] = jnp.sum(mask.astype(jnp.float32))
            if guard is not None:
                metrics["mask_eff"] = mask.astype(jnp.float32)
        if t_cp is not None and t_cm is not None:
            cmask = mask if clock_mask is None else clock_mask
            assert cmask is not None, "in-graph clock needs a clock_mask/mask"
            v = env["v_count"] if envelope else V
            metrics.update(_masked_clock(t_cp, t_cm, cmask, v))
        return agg_p, new_s, metrics

    return round_step


def build_round_chunk(
    loss_fn: Callable,
    opt: Optimizer,
    V: int,
    n_clients: int,
    aggregation: str = "allreduce",
    impl: str = "xla",
    scenario: bool = False,
    batch_from: Callable = None,
    update_bits: float = None,
    envelope: bool = False,
    guard=None,
    faults: bool = False,
    sampled: bool = False,
    quorum: str = None,
    mesh=None,
    param_specs_tree=None,
    client_axes=None,
):
    """Fuse a whole chunk of rounds into one `jax.lax.scan` over the round
    step: the host touches the device once per chunk instead of once per
    round (one stacked input transfer in, one stacked metrics fetch out).

    Returns chunk_step(params_C, opt_C, key, weights, t_cp, data, xs)
    -> (params_C', opt_C', key', ys) where xs is the per-round scanned
    input pytree, every leaf stacked on a leading R axis:

      batches  (R, C, V, ...) pre-stacked batch pytree (generic path), OR
      idx      (R, C, V, B) int32 global sample indices, gathered in-graph
               from the device-resident `data` arrays via `batch_from`
               (zero per-round batch bytes over PCIe/host memory)
      valid    (R,) bool — padding flag for a ragged final chunk. Invalid
               rounds run (shapes are static) but their state writes and
               PRNG-key advance are masked out, so a chunk padded from n
               to R rounds leaves params/opt/key exactly as n rounds would
               — and every chunk of a run reuses ONE trace.
      mask, clock_mask, t_cm   (R, C) scenario inputs (scenario=True),
               with t_cp the static (C,) compute times (Eq. 4).

    ys stacks per-round metrics: 'loss' (and with scenario=True
    'n_participants', the in-graph Eq. 8 clocks 'T_cm'/'T_cp'/'T_round');
    with update_bits set, 'uplink_bits' = participants x bits-per-update
    (compression.compressed_bits accounting, computed in-graph in fp32 —
    callers needing exact counts multiply on the host). The caller fetches
    ys with a single device_get per chunk. Note FLSimulation's history
    records rebuild clocks/bits from the f64 host twin of the same inputs
    (delay.chunk_round_times — bit parity with the per-round backends);
    the fp32 in-graph copies exist for device-side consumers that must
    not touch the host (custom in-graph stopping rules, on-device logs).

    aggregation='int8_stochastic' draws per-client quantizer keys inside
    the scan body through compression.sequential_client_keys — the same
    schedule as the per-round backends, so the stochastic-rounding noise
    stream is bit-identical to theirs.

    envelope=True builds the Study API's (V, b) shape-envelope chunk:
    `loss_fn` is the masked form, V is the padded V_env (batches/idx carry
    (C, V_env, B_env) per round), and the chunk fn gains a trailing `env`
    argument — {'v_mask', 'sample_mask', 'n_samples', 'v_count',
    'update_bits'} traced per-member values (see build_round_step). The
    in-graph uplink_bits then uses env['update_bits'] (traced, so arms
    with different wire sizes share one compiled graph) instead of the
    static update_bits constant.

    The fault layer (faults.FaultModel) adds two static build knobs that
    keep everything in the ONE compiled scan:
      guard        static (max_norm, reject) sanitation pair, forwarded
                   to build_round_step — rejected clients count out of
                   'loss'/'n_participants' via the post-guard 'mask_eff'.
      faults=True  xs gains two traced (R,) leaves: 't_cap' (the round
                   deadline in seconds, +inf when none — the in-graph
                   'T_round' becomes min(t_cap, straggler max)) and
                   'bits_mult' (total uplink ATTEMPTS this round — with
                   retransmission every attempt's bits hit the air, so
                   'uplink_bits' = bits_mult x bits-per-update instead of
                   participants x bits). Deadline/retry exclusions are
                   drawn host-side into the mask (simulation._fault_round)
                   — the graph only consumes their traced results, so
                   fault rounds neither retrace nor sync. ys additionally
                   stacks 'finite' (R, C) — each round's per-client
                   finite-loss mask, the DivergenceError diagnostic.

    quorum (static; None | 'reject' | 'accept') compiles the quorum gate
    in-graph: xs gains a traced (R,) leaf 'quorum_min' (the round's
    minimum participant count) and ys a per-round 'rejected' flag
    (post-guard participation < quorum_min). Under 'reject' the xs also
    carry 'q_penalty' (R,) re-dispatch seconds: a rejected round's
    params/opt writes are masked out exactly like an invalid padded round
    (the model never sees it) while the PRNG key still advances (the
    compression keys were drawn — the per-round backends' stream does the
    same), and the in-graph 'T_round' gains the penalty. 'accept' only
    raises the flag. quorum=None builds a byte-identical graph to
    pre-quorum code — no extra ops, no extra xs leaves.

    sampled=True builds the K-cohort form of the chunk (sampled
    participation: n_clients = K lanes, each round occupied by a freshly
    gathered cohort of the M-client population). Lanes change owners
    every round, so the per-lane FedAvg weights and Eq. 4 compute times
    stop being chunk constants and ride in xs instead — two extra traced
    leaves 'weights' (R, K) and 't_cp' (R, K); callers pass the
    positional `weights`/`t_cp` chunk args as None. Everything else —
    masks, clocks, faults, envelope, compression keys (lane-indexed) — is
    unchanged, and at K = M (cohort == arange(M) every round) the xs rows
    equal the dense chunk constants, so the math is value-identical to
    the dense graph.

    aggregation='allreduce_shardmap' shards the client axis over `mesh`
    (forwarding mesh/param_specs_tree/client_axes to build_round_step):
    each device reduces its own client rows locally and one param-sized
    psum crosses the wire per round.
    """
    from repro.federated import compression

    if quorum not in (None, "reject", "accept"):
        raise ValueError(
            f"quorum must be None, 'reject' or 'accept', got {quorum!r}")
    if quorum is not None and not scenario:
        raise ValueError("quorum gating needs the scenario path "
                         "(participation masks) — scenario=True")
    step = build_round_step(loss_fn, opt, V, aggregation=aggregation,
                            mesh=mesh, param_specs_tree=param_specs_tree,
                            client_axes=client_axes,
                            impl=impl, envelope=envelope, guard=guard)
    compress = aggregation == "int8_stochastic"

    def chunk_step(params_C, opt_C, key, weights, t_cp, data, xs, env=None):
        bits = (env["update_bits"] if envelope
                else (None if update_bits is None
                      else jnp.float32(update_bits)))

        def body(carry, x):
            params, opt_state, k = carry
            w_r = x["weights"] if sampled else weights
            t_cp_r = x["t_cp"] if sampled else t_cp
            if batch_from is not None:
                batches = batch_from(data, x["idx"])
            else:
                batches = x["batches"]
            new_key, keys_C = k, None
            if compress:
                new_key, keys_C = compression.sequential_client_keys(
                    k, n_clients)
            if scenario:
                new_p, new_s, m = step(
                    params, opt_state, batches, w_r, keys=keys_C,
                    mask=x["mask"], clock_mask=x["clock_mask"],
                    t_cp=t_cp_r, t_cm=x["t_cm"], env=env)
                # Mean over participating clients; NaN on a zero-
                # participation round (same formula as the per-round
                # backends, for bit parity). With a guard, participation
                # is the post-sanitation mask.
                msk = m.get("mask_eff", x["mask"])
                n = jnp.sum(msk)
                loss = (jnp.sum(m["per_client_loss"] * msk)
                        / jnp.where(n > 0, n, 1.0))
                loss = jnp.where(n > 0, loss, jnp.nan)
                T_round = m["T_round"]
                if faults:
                    T_round = jnp.minimum(x["t_cap"], T_round)
                rejected = None
                if quorum is not None:
                    # Quorum gate on the POST-guard participation: below
                    # quorum raises the flag; 'reject' additionally pays
                    # the re-dispatch penalty in the in-graph clock (the
                    # host f64 twin mirrors it) and no-ops the state
                    # writes below.
                    rejected = n < x["quorum_min"]
                    if quorum == "reject":
                        T_round = T_round + jnp.where(
                            rejected, x["q_penalty"], 0.0)
                ys = {"loss": loss, "n_participants": n,
                      "T_cm": m["T_cm"], "T_cp": m["T_cp"],
                      "T_round": T_round}
                if rejected is not None:
                    ys["rejected"] = rejected
                if faults:
                    # Per-client finite-loss mask: the DivergenceError
                    # diagnostic (which clients were still finite on the
                    # offending round).
                    ys["finite"] = jnp.isfinite(m["per_client_loss"])
                if bits is not None:
                    ys["uplink_bits"] = (x["bits_mult"] * bits if faults
                                         else n * bits)
            else:
                rejected = None
                new_p, new_s, m = step(
                    params, opt_state, batches, w_r, keys=keys_C,
                    env=env)
                ys = {"loss": jnp.mean(m["per_client_loss"])}
                if bits is not None:
                    ys["uplink_bits"] = n_clients * bits
            valid = x["valid"]
            ok = valid
            if quorum == "reject":
                # A quorum-rejected round is the padded-round trick
                # applied in-graph: params/opt keep their pre-round
                # values. The PRNG key still advances (its compression
                # keys were drawn — the per-round backends consume the
                # stream identically), unlike a padded round's.
                ok = jnp.logical_and(valid, jnp.logical_not(rejected))
            keep = lambda nw, old: jnp.where(ok, nw, old.astype(nw.dtype))  # noqa: E731
            new_p = jax.tree.map(keep, new_p, params)
            new_s = jax.tree.map(keep, new_s, opt_state)
            new_key = jnp.where(valid, new_key, k)
            return (new_p, new_s, new_key), ys

        (params_C, opt_C, key), ys = jax.lax.scan(
            body, (params_C, opt_C, key), xs)
        return params_C, opt_C, key, ys

    return chunk_step


def build_async_chunk(
    loss_fn: Callable,
    opt: Optimizer,
    V: int,
    n_clients: int,
    spec,  # events.AsyncSpec — static policy (buffer size, staleness, mode)
    impl: str = "xla",
    batch_from: Callable = None,
    compress: bool = False,
):
    """Fuse a whole event-budget chunk of the asynchronous server into one
    `jax.lax.scan`: the scan axis is ARRIVAL EVENTS, not rounds, and the
    carry holds a device-side pending-update structure — a (C,) finish-time
    array whose argmin is the compiled analogue of a priority-queue pop.
    No Python event loop: E events cost one dispatch.

    Returns chunk_step(params_C, opt_C, key, async_c, sizes, data, xs)
    -> (params_C', opt_C', key', async_c', ys).

    async_c is the async carry dict (the extra SimState leaves):
      params_g   the server's global model (unstacked param tree)
      buf        staleness-weighted delta accumulator (f32 param tree)
      buf_w      f32 sum of accepted weights in the buffer
      cnt        int32 number of buffered updates
      loss_sum   f32 sum of accepted updates' local losses
      t_finish   (C,) f32 ABSOLUTE finish time of each client's in-flight
                 dispatch (the pending-update structure); +inf marks a
                 client blocked awaiting the aggregation ack
      t_next     (C,) f32 service time of the NEXT dispatch a blocked
                 client was handed (applied at its release)
      now        f32 event clock (arrival time of the last valid event)
      version    int32 server aggregation count
      version_C  (C,) int32 server version each client was dispatched at
      drop_C     (C,) f32 1.0 where the in-flight update will be lost
                 (participation mask / fault realization, resolved at
                 dispatch time)

    params_C/opt_C keep the synchronous layout — row c is the params/opt
    snapshot client c was DISPATCHED with (rows now differ between
    aggregations, unlike the sync backends' identical post-broadcast rows).

    xs leaves, every one stacked on a leading (E,) event axis:
      t_svc      (E, C) f32 service time (V t_cp + effective uplink) of the
                 dispatch HANDED OUT at this event, drawn M-wide per event
                 (prefix-stable stream consumption); only the arriving
                 client's column is consumed
      drop_next  (E, C) f32 loss indicator for that dispatch
      valid      (E,) padding flag — invalid events run but every state
                 write is masked out, exactly the sync chunk's ragged-tail
                 trick, so one trace serves every chunk of a run
      idx/batches  the ARRIVING client's V local batches — (E, V, B) int32
                 gather indices (device-resident data) or (E, V, ...)
                 pre-stacked host batches. The host knows who arrives at
                 each event ahead of dispatch via the f32 schedule twin
                 (events.twin_step): jnp.argmin == np.argmin (first-min
                 tie-break) over IEEE-identical f32 adds.

    Per event: pop c = argmin(t_finish); run c's V local steps from its
    dispatch snapshot; weight the delta by w = w_stale(version -
    version_C[c]) * sizes[c] (events.staleness_weight); a non-dropped
    update enters the buffer, and the K-th buffered update fires the
    aggregation params_g += buf / buf_w (mode='fedbuff' — a weighted mean
    of deltas, which in the sync limit K=M / uniform scenario equals
    FedAvg's weighted mean up to the delta-form association; see
    EXPERIMENTS.md §Asynchronous execution) or the immediate mixing
    params_g = (1 - lr w_stale) params_g + lr w_stale new_p
    (mode='fedasync', K=1). Re-dispatch is ACK-AT-AGGREGATION: an
    accepted update's client blocks until the aggregation that consumes
    its update, then re-dispatches from the fresh aggregate at the fill
    instant (finish time now + t_svc[e, c]); a dropped update's client
    re-dispatches immediately from the current global model. The K=M
    sync limit is therefore EXACTLY FedAvg's broadcast schedule.

    ys per event: t_event, client, dropped, agg (buffer filled here),
    loss_agg (mean buffered loss at a fill, NaN otherwise), staleness,
    version and cnt after the event — the event-aligned metrics the
    simulator turns into per-aggregation RoundRecords.
    """
    from repro.federated import compression, events as ev

    local = local_steps_fn(loss_fn, opt)
    K = int(spec.buffer_size)
    fedasync = spec.mode == "fedasync"

    def chunk_step(params_C, opt_C, key, async_c, sizes, data, xs):
        sizes_f32 = sizes.astype(jnp.float32)

        def body(carry, x):
            params_C, opt_C, k, a = carry
            valid = x["valid"]
            t_finish = a["t_finish"]
            # Priority-queue pop, compiled: earliest finisher arrives.
            # First-minimum tie-break == np.argmin, the twin contract.
            c = jnp.argmin(t_finish)
            now = t_finish[c]
            p_c = jax.tree.map(lambda t: t[c], params_C)
            s_c = jax.tree.map(lambda t: t[c], opt_C)
            if batch_from is not None:
                batches = batch_from(data, x["idx"])
            else:
                batches = x["batches"]
            new_p, new_s, loss = local(p_c, s_c, batches)
            delta = jax.tree.map(
                lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
                new_p, p_c)
            new_key = k
            if compress:
                # One quantizer key per event — the async twin of the sync
                # backends' per-round sequential_client_keys schedule.
                new_key, keys_1 = compression.sequential_client_keys(k, 1)
                delta = compression.decompress_update(
                    compression.compress_update(
                        delta, keys_1[0], impl=impl), impl=impl)
            drop = a["drop_C"][c]
            stale = (a["version"] - a["version_C"][c]).astype(jnp.float32)
            ws = ev.staleness_weight(spec, stale, xp=jnp)
            w = ws * sizes_f32[c]
            take = jnp.logical_and(valid, drop == 0)
            takef = take.astype(jnp.float32)
            onehot_c = jnp.arange(n_clients) == c
            # Buffer entry (exact +0.0 when dropped/invalid — the update
            # cannot perturb the aggregate's bits, same discipline as the
            # sync path's masked weighted sum).
            buf = jax.tree.map(lambda b, d: b + takef * (w * d),
                               a["buf"], delta)
            buf_w = a["buf_w"] + takef * w
            cnt = a["cnt"] + take.astype(jnp.int32)
            loss_sum = a["loss_sum"] + takef * loss
            fill = take if fedasync else jnp.logical_and(take, cnt >= K)
            if fedasync:
                am = jnp.where(fill, jnp.float32(spec.server_lr) * ws, 0.0)
                params_g = jax.tree.map(
                    lambda g, n: ((jnp.float32(1.0) - am)
                                  * g.astype(jnp.float32)
                                  + am * n.astype(jnp.float32)
                                  ).astype(g.dtype),
                    a["params_g"], new_p)
            else:
                denom = jnp.where(fill, buf_w, jnp.float32(1.0))
                params_g = jax.tree.map(
                    lambda g, b: jnp.where(
                        fill, g.astype(jnp.float32) + b / denom,
                        g.astype(jnp.float32)).astype(g.dtype),
                    a["params_g"], buf)
            version = a["version"] + fill.astype(jnp.int32)
            loss_agg = jnp.where(
                fill, loss_sum / jnp.maximum(cnt.astype(jnp.float32), 1.0),
                jnp.nan)
            # Aggregation drains the buffer.
            buf = jax.tree.map(
                lambda b: jnp.where(fill, jnp.zeros_like(b), b), buf)
            buf_w = jnp.where(fill, jnp.float32(0.0), buf_w)
            cnt = jnp.where(fill, jnp.int32(0), cnt)
            loss_sum = jnp.where(fill, jnp.float32(0.0), loss_sum)
            # Ack-at-aggregation re-dispatch (all writes valid-masked so
            # padded events are exact no-ops): an ACCEPTED update's client
            # blocks (finish time +inf) holding its next service draw, and
            # is released — re-dispatched FROM THE FRESH AGGREGATE at the
            # fill instant — by the aggregation that consumes its update
            # (the server's model broadcast is the ack). A DROPPED
            # update's client re-dispatches immediately from the current
            # global model (the server never saw it). This is what makes
            # the K=M sync limit EXACT: every generation starts from the
            # just-aggregated model, like FedAvg's broadcast (see
            # EXPERIMENTS.md §Asynchronous execution).
            t_next = jax.tree.map(
                lambda t: t.at[c].set(
                    jnp.where(take, x["t_svc"][c], t[c])), a["t_next"])
            t_fin = t_finish.at[c].set(jnp.where(
                valid,
                jnp.where(take, jnp.float32(jnp.inf),
                          now + x["t_svc"][c]),
                t_finish[c]))
            idle = jnp.isinf(t_fin)
            release = jnp.logical_and(fill, idle)  # includes c itself
            t_fin = jnp.where(release, now + t_next, t_fin)
            version_C = a["version_C"].at[c].set(
                jnp.where(valid, version, a["version_C"][c]))
            version_C = jnp.where(release, version, version_C)
            # Model binding: dropped -> rebind row c to the current global
            # now; released -> rebind every idle row to the fresh
            # aggregate. (fill == False on a drop, so params_g is the
            # right model in both cases.)
            bind = jnp.logical_or(
                release,
                jnp.logical_and(onehot_c,
                                jnp.logical_and(valid,
                                                jnp.logical_not(take))))
            params_C = jax.tree.map(
                lambda t, g: jnp.where(
                    bind.reshape((-1,) + (1,) * (t.ndim - 1)),
                    g.astype(t.dtype), t),
                params_C, params_g)
            opt_C = jax.tree.map(
                lambda t, n: t.at[c].set(
                    jnp.where(valid, n.astype(t.dtype), t[c])),
                opt_C, new_s)
            a2 = {
                "params_g": params_g,
                "buf": buf,
                "buf_w": buf_w,
                "cnt": cnt,
                "loss_sum": loss_sum,
                "t_finish": t_fin,
                "t_next": t_next,
                "now": jnp.where(valid, now, a["now"]),
                "version": version,
                "version_C": version_C,
                "drop_C": a["drop_C"].at[c].set(
                    jnp.where(valid, x["drop_next"][c], drop)),
            }
            ys = {"t_event": now, "client": c.astype(jnp.int32),
                  "dropped": drop, "agg": fill, "loss_agg": loss_agg,
                  "staleness": jnp.where(take, stale, 0.0),
                  "version": version, "cnt": cnt}
            return (params_C, opt_C, jnp.where(valid, new_key, k), a2), ys

        (params_C, opt_C, key, async_c), ys = jax.lax.scan(
            body, (params_C, opt_C, key, async_c), xs)
        return params_C, opt_C, key, async_c, ys

    return chunk_step


def build_fleet_chunk(chunk_step: Callable, envelope: bool = False,
                      sampled: bool = False) -> Callable:
    """vmap a `build_round_chunk` step over a leading fleet axis S.

    The chunk step is pure and closure-free over run state (everything it
    touches rides in as arguments), so a whole fleet — S seeds, or S arms
    sharing one (model, b, V, M) shape signature — executes as ONE
    dispatch per chunk instead of S sequential chunk calls:

      carry (params_C, opt_C, key)  (S, C, ...) / (S, 2)   mapped, axis 0
      weights, data                 shared, broadcast (in_axes=None) —
                                    one population / one device-resident
                                    dataset upload serves the whole fleet
      t_cp                          shared when all members run one batch
                                    size; per-member (mapped axis 0) under
                                    envelope=True, where b varies by arm
      xs                            every leaf (S, R, ...), mapped axis 0
      env (envelope=True only)      per-member (V, b) masks, mapped axis 0

    ys leaves come back stacked (S, R). Per-member math is exactly the
    single-chunk graph batched over S (vmap is a compile-time transform,
    not a loop), which is what makes the per-seed results bit-identical to
    sequential runs — asserted in tests/test_experiment_api.py (seeds) and
    tests/test_study.py (mixed-(b, V) arm groups).

    sampled=True (cohort chunks): per-round weights/t_cp live in xs
    (mapped, per-member cohorts differ) and the positional weights/t_cp
    args are None, so their in_axes must be None even under envelope.
    """
    if envelope:
        t_axis = None if sampled else 0
        return jax.vmap(chunk_step,
                        in_axes=(0, 0, 0, None, t_axis, None, 0, 0))
    return jax.vmap(chunk_step, in_axes=(0, 0, 0, None, None, None, 0))


def replicate_clients(tree: Any, n_clients: int) -> Any:
    """Stack identical client copies on a new leading axis."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_clients, *x.shape)), tree)
