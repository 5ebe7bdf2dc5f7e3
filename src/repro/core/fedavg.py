"""Federated optimization loop: FedAvg / FedProx clients + THGS/secure-agg server.

The transmitted "gradient update" of the paper is the local model delta after
``local_steps`` of SGD (McMahan et al. 2017); THGS + secure aggregation compress
that delta. This module is the single-host reference implementation used by the
paper-scale benchmarks and tests; the datacenter-mesh variant lives in
repro/launch/train.py and shares the encode/aggregate engine (core/streams.py).

Since the stream-engine refactor (DESIGN.md §3) a round is three batched,
jitted programs instead of a per-client Python loop:

  1. ``batched_client_update`` — local SGD for every participant, vmapped over
     the stacked client batches (one XLA dispatch per round);
  2. ``streams.encode_leaf_batch`` per leaf — the unified top-k ∪ mask-support
     encode for all clients at once (counter-based pair seeds from the
     repro/secagg round protocol: DH-agreed pair secrets, Shamir-shared for
     dropout recovery);
  3. ``streams.decode_leaf_batch`` per leaf — one fused scatter-add over every
     client's stream, with per-client weights, survivor gating and Bonawitz
     reconstruction of dropped clients' unpaired masks from their
     Shamir-recombined keys (protocol phase 3).

Weighted aggregation is client-side (weights scale the gradient values before
masking, so non-uniform weights keep mask cancellation exact); the server
normalizes by the survivors' total weight after the masks have cancelled.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import costs, schedules
from repro.core import streams as se
from repro.core.types import (
    CommRecord,
    FedConfig,
    PyTree,
    SecureAggConfig,
    THGSConfig,
    tree_zeros_like,
)

LossFn = Callable[[PyTree, Any], jax.Array]


def _client_update(
    params: PyTree,
    batches: Any,  # stacked leading axis = local_steps
    loss_fn: LossFn,
    local_steps: int,
    lr: float,
    prox_mu: float = 0.0,
) -> tuple[PyTree, jax.Array]:
    """Local SGD (optionally FedProx-proximal); returns (delta, mean loss)."""
    grad_fn = jax.value_and_grad(loss_fn)

    def prox_term(p):
        if prox_mu == 0.0:
            return 0.0
        sq = sum(
            jnp.sum((a - b) ** 2)
            for a, b in zip(jax.tree_util.tree_leaves(p),
                            jax.tree_util.tree_leaves(params))
        )
        return 0.5 * prox_mu * sq

    def step(p, batch):
        loss, g = grad_fn(p, batch)
        if prox_mu != 0.0:
            gp = jax.grad(lambda q: prox_term(q))(p)
            g = jax.tree_util.tree_map(jnp.add, g, gp)
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
        return p, loss

    new_params, losses = jax.lax.scan(
        step, params, batches, length=local_steps
    )
    delta = jax.tree_util.tree_map(lambda a, b: a - b, new_params, params)
    return delta, jnp.mean(losses)


@partial(jax.jit, static_argnames=("loss_fn", "local_steps", "prox_mu"))
def client_update(
    params: PyTree,
    batches: Any,
    loss_fn: LossFn,
    local_steps: int,
    lr: float,
    prox_mu: float = 0.0,
) -> tuple[PyTree, jax.Array]:
    """Single-client entry (kept for callers that step one client at a time)."""
    return _client_update(params, batches, loss_fn, local_steps, lr, prox_mu)


@partial(jax.jit, static_argnames=("loss_fn", "local_steps", "prox_mu"))
def batched_client_update(
    params: PyTree,
    batches_stacked: Any,   # leading axis = clients, then local_steps
    loss_fn: LossFn,
    local_steps: int,
    lr: float,
    prox_mu: float = 0.0,
) -> tuple[PyTree, jax.Array]:
    """All participants' local SGD in one vmapped program.

    Returns (deltas stacked [C, ...], losses [C])."""
    return jax.vmap(
        lambda b: _client_update(params, b, loss_fn, local_steps, lr, prox_mu)
    )(batches_stacked)


@functools.lru_cache(maxsize=None)
def _sharded_update_program(mesh, loss_fn: LossFn, local_steps: int,
                            prox_mu: float):
    """Cached shard_map twin of ``batched_client_update`` for a clients mesh."""
    P = jax.sharding.PartitionSpec

    def body(params, batches_l, lr):
        return jax.vmap(
            lambda b: _client_update(params, b, loss_fn, local_steps, lr,
                                     prox_mu)
        )(batches_l)

    fn = se.shard_map_clients(
        body, mesh,
        in_specs=(P(), P(se.CLIENT_AXIS), P()),
        out_specs=(P(se.CLIENT_AXIS), P(se.CLIENT_AXIS)))
    return jax.jit(fn)


def batched_client_update_sharded(
    mesh,
    params: PyTree,
    batches_stacked: Any,   # leading axis = clients, then local_steps
    loss_fn: LossFn,
    local_steps: int,
    lr: float,
    prox_mu: float = 0.0,
) -> tuple[PyTree, jax.Array]:
    """Device-sharded local SGD: clients partitioned over the ``clients``
    mesh axis, each device vmapping its shard through the same
    ``_client_update`` program. Per-client math is independent, so deltas are
    bit-exact with ``batched_client_update`` (losses may differ in the last
    ulp from reduction layout; the parity tests pin the deltas and the
    decoded server update)."""
    fn = _sharded_update_program(mesh, loss_fn, local_steps, float(prox_mu))
    return fn(params, batches_stacked, lr)


@dataclasses.dataclass
class FederatedState:
    params: PyTree
    residuals: dict[int, PyTree]        # per-client error feedback
    losses: dict[int, float]            # last local loss per client (for Eq. 2 beta)
    round: int = 0
    comm_log: list[CommRecord] = dataclasses.field(default_factory=list)


def init_state(params: PyTree, fed: FedConfig) -> FederatedState:
    return FederatedState(
        params=params,
        residuals={c: tree_zeros_like(params) for c in range(fed.n_clients)},
        losses={},
    )


def _mean_or_none(vals):
    vals = [v for v in vals if v is not None]
    return float(sum(vals) / len(vals)) if vals else None


def run_round(
    state: FederatedState,
    client_batches: dict[int, Any],
    loss_fn: LossFn,
    fed: FedConfig,
    thgs: THGSConfig | None,
    sa: SecureAggConfig,
    bits: costs.BitModel = costs.PAPER_BITS,
    client_weights: Mapping[int, float] | None = None,
    dropped: Sequence[int] = (),
    protocol=None,
    mesh=None,
    codec: str = "f32",
    topology: str = "flat",
    tree_groups: int = 0,
    dp=None,
) -> FederatedState:
    """One aggregation round over the provided participating clients.

    thgs=None -> dense FedAvg/FedProx baseline (optionally dense-masked SA).
    ``client_weights`` gives per-client aggregation weights (e.g. local data
    counts); unweighted clients default to 1. ``dropped`` lists participants
    that completed the mask agreement but whose upload never arrived — their
    streams are excluded and the survivors' unpaired masks toward them are
    regenerated from Shamir-reconstructed pair seeds and cancelled server-side
    (Bonawitz dropout recovery, repro/secagg/protocol.py; raises
    ``secagg.ThresholdError`` when fewer than the Shamir threshold survive).
    ``protocol`` injects a pre-built ``RoundProtocol`` (tests); by default the
    round runs its own setup over the participants.

    ``mesh`` opts into the device-sharded client-parallel round (DESIGN.md
    §11): a 1-D ``clients`` mesh (launch/mesh.clients_mesh_for) partitions the
    cohort over devices — local SGD, THGS encode and pair-mask PRNG run
    per-shard under shard_map, and the server update is one sparse-stream
    all_gather + the identical fused scatter-add, bit-exact with the vmap
    path. When the mesh cannot host the cohort (None, 1 device, or cohort not
    divisible) the single-device vmap path runs, unchanged.

    ``codec`` selects the stream wire format (core/codecs.py, DESIGN.md §12):
    ``'f32'`` is the passthrough; ``'int8'``/``'int4'``/``'1bit'`` quantize
    the stream values (quantization error absorbed into the THGS error
    feedback) and delta-pack the indices, and the round is accounted at the
    exact packed wire size. Quantized codecs require THGS and are rejected
    under secure aggregation — pair masks cancel bit-exactly only on the f32
    grid.

    ``topology`` selects the aggregation tree (DESIGN.md §13): ``'flat'`` is
    the single fused scatter-add; ``'tree'`` splits the decode across
    ``tree_groups`` sub-aggregators (0 = auto, ~sqrt(cohort)), each owning a
    contiguous index range of the dense buffer, combined by concatenation —
    bit-exact with flat (params, residuals, CommLedger), including secagg
    dropout recovery, for any group count. Requires THGS.

    ``dp`` takes a ``core.dp.DPConfig`` (DESIGN.md §15): per-client global-L2
    clipping of the error-feedback accumulator ``residual + delta`` (the
    encoder's actual input, so the bound covers the full emitted stream),
    and with ``sigma > 0`` the round releases gradient values on a PUBLIC
    common support (no data-dependent index leakage) with grid-exact
    Gaussian noise on every released slot, injected under the pair masks and
    seeded per (round, client) so resume replays it. Requires THGS, the f32
    codec, and uniform client weights — non-uniform ``client_weights`` are
    rejected here (a weighted stream would scale a contribution past the
    clip bound S), mirroring the sim config's ``weight_by_data_count``
    rejection. ``None`` or an inactive config (``clip=inf, sigma=0``) leaves
    the round bit-identical to the pre-DP path.

    All participants' batch pytrees must share one structure and one set of
    array shapes (they are stacked on a leading client axis for the batched
    local-SGD program); pad ragged local data to fixed [steps, batch] first,
    as data/federated.py::client_batches does.
    """
    with TraceAnnotation("fl.local_sgd"):
        if topology not in ("flat", "tree"):
            raise ValueError(f"unknown topology {topology!r}")
        if topology == "tree" and thgs is None:
            raise ValueError("topology='tree' requires THGS sparse streams; "
                             "dense rounds have no stream decode to shard")
        dp_active = dp is not None and dp.active
        if dp_active:
            dp.validate()
            if thgs is None:
                raise ValueError(
                    "dp requires THGS sparse streams; the DP noise rides the "
                    "unified stream's transmitted slots (thgs is None)")
            from repro.core.dp import reject_codec_with_noise

            reject_codec_with_noise(codec, dp.sigma)
            if client_weights and any(
                    float(w) != 1.0 for w in client_weights.values()):
                raise ValueError(
                    "dp requires uniform client weights: weights scale the "
                    "stream values before masking, so a weight != 1.0 would "
                    "scale that client's contribution past the clip bound S "
                    "the accountant calibrates noise against")
        participants = sorted(client_batches.keys())
        C = len(participants)
        sharded = se.can_shard_clients(mesh, C)
        dropped = set(dropped)
        assert dropped <= set(participants), "dropped must be participants"
        survivors = [c for c in participants if c not in dropped]
        assert survivors, "a round needs at least one surviving client"
        alive = jnp.asarray([c not in dropped for c in participants], bool)
        w_list = [float(client_weights.get(c, 1.0)) if client_weights
                  else 1.0 for c in participants]
        w_vec = jnp.asarray(w_list, jnp.float32)
        w_surv_total = sum(w for w, c in zip(w_list, participants)
                           if c not in dropped)

        leaves, treedef = jax.tree_util.tree_flatten(state.params)
        leaf_shapes = [x.shape for x in leaves]
        leaf_dtypes = [x.dtype for x in leaves]
        leaf_sizes = [x.size for x in leaves]
        model_size = sum(leaf_sizes)

        # ---- 1. all clients' local SGD, one vmapped dispatch ----
        batches_stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[client_batches[c] for c in participants])
        if sharded:
            batches_stacked = se.shard_client_tree(batches_stacked, mesh)
            deltas_stacked, losses = batched_client_update_sharded(
                mesh,
                state.params,
                batches_stacked,
                loss_fn,
                fed.local_steps,
                fed.local_lr,
                fed.prox_mu if fed.algorithm == "fedprox" else 0.0,
            )
        else:
            deltas_stacked, losses = batched_client_update(
                state.params,
                batches_stacked,
                loss_fn,
                fed.local_steps,
                fed.local_lr,
                fed.prox_mu if fed.algorithm == "fedprox" else 0.0,
            )
    with TraceAnnotation("fl.host_read", values=C):
        losses_list = [float(x) for x in losses]

    if thgs is not None:
        with TraceAnnotation("fl.schedule"):
            # Eq. 2's beta from the federation-mean loss trajectory: one
            # static per-leaf k for the whole batched round (per-client k
            # would make the stacked stream shapes ragged — see DESIGN.md §3).
            loss_prev = _mean_or_none(
                [state.losses.get(c) for c in participants])
            loss_curr = _mean_or_none(losses_list)
            ks = schedules.leaf_ks(
                thgs,
                leaf_sizes,
                t=state.round,
                total_rounds=fed.rounds,
                loss_prev=loss_prev,
                loss_curr=loss_curr,
            )
            use_masks = sa.enabled and C >= 2
            se.reject_codec_with_masks(codec, use_masks)
            k_masks = [sa.k_mask_for(size, C) if use_masks else 0
                       for size in leaf_sizes]
        if use_masks:
            # the round protocol: DH pair secrets + Shamir shares (phases
            # 0-1); layering note — secagg sits beside core, this local
            # import is the one sanctioned upward edge (DESIGN.md §10)
            from repro.secagg.protocol import RoundProtocol

            proto = (protocol if protocol is not None
                     else RoundProtocol.setup(sa, participants, state.round))
            pair_seeds, pair_signs = proto.pair_seed_matrix()
            recovery_seeds = (proto.recover_seeds(survivors, sorted(dropped))
                              if dropped else None)
        else:
            proto = None
            pair_seeds = pair_signs = recovery_seeds = None

        with TraceAnnotation("fl.restack"):
            # per-(round, client) noise seeds and the round's public common-
            # support seed, derived host-side so the stream is replayable
            # from config + round alone (resume, sharded parity)
            dp_sigma_c = dp.sigma_client(C) if dp_active else 0.0
            dp_noised = dp_active and dp.noised
            dp_seeds = (
                jnp.asarray(dp.client_seeds(state.round, participants))
                if dp_noised else None)
            dp_sup_seed = dp.support_seed(state.round) if dp_noised else 0

            delta_leaves = jax.tree_util.tree_leaves(deltas_stacked)
            res_per_client = [jax.tree_util.tree_leaves(state.residuals[c])
                              for c in participants]
            res_stacked = [jnp.stack([rl[i] for rl in res_per_client])
                           for i in range(len(leaves))]
            if dp_active and dp.clips:
                # per-client global-L2 clip of the ENCODER INPUT — the error-
                # feedback accumulator residual + delta — so the sensitivity
                # bound S holds for the full stream the client emits (the
                # residual carries untransmitted mass across rounds; clipping
                # the fresh delta alone would not bound it). The clipped
                # accumulator becomes the encode's update with a zeroed
                # residual source; compliant clients scale by exactly 1.0
                # (core/dp.py).
                from repro.core.dp import clip_client_updates

                acc_tree = jax.tree_util.tree_unflatten(
                    treedef,
                    [d.astype(jnp.float32) + r.astype(jnp.float32)
                     for d, r in zip(delta_leaves, res_stacked)])
                delta_leaves = jax.tree_util.tree_leaves(
                    clip_client_updates(acc_tree, clip=float(dp.clip)))
                res_stacked = [jnp.zeros_like(r) for r in res_stacked]
            if sharded:
                res_stacked = [se.shard_client_tree(r, mesh)
                               for r in res_stacked]

            groups = tree_groups if tree_groups > 0 else max(
                2, int(round(C ** 0.5)))

        agg_leaves, new_res_leaves = [], []
        for leaf_id, (d_st, r_st, k, k_mask, size, shape) in enumerate(
                zip(delta_leaves, res_stacked, ks, k_masks, leaf_sizes,
                    leaf_shapes)):
            if not sharded:
                # ---- 2. batched unified-stream encode (all clients, one
                # jit) ----
                with TraceAnnotation("fl.encode", leaf=leaf_id):
                    streams_b, new_res = se.encode_leaf_batch(
                        d_st, r_st, k=k, nb=1, m=size, size=size,
                        selector=thgs.selector, sample_frac=thgs.sample_frac,
                        pair_seeds=pair_seeds, pair_signs=pair_signs,
                        k_mask=k_mask, mask_p=sa.p, mask_q=sa.q,
                        leaf_id=leaf_id, weights=w_vec, codec=codec,
                        dp_sigma=dp_sigma_c, dp_seeds=dp_seeds,
                        dp_support_seed=dp_sup_seed)
            splits = (se.tree_splits(size, groups) if topology == "tree"
                      else ())
            with TraceAnnotation(
                    "fl.encode_decode" if sharded else "fl.decode",
                    leaf=leaf_id,
                    scatter_steps=se.scatter_steps(
                        C, k, k_mask, 1, size, recovery=bool(dropped),
                        splits=splits)):
                if sharded:
                    # ---- 2+3. client-parallel encode + fused decode: one
                    # shard_map program per leaf (DESIGN.md §11) ----
                    dense, new_res = se.encode_decode_leaf_sharded(
                        mesh, d_st, r_st, k=k, nb=1, m=size, size=size,
                        selector=thgs.selector, sample_frac=thgs.sample_frac,
                        pair_seeds=pair_seeds, pair_signs=pair_signs,
                        recovery_seeds=recovery_seeds if dropped else None,
                        alive=alive if dropped else None,
                        k_mask=k_mask, mask_p=sa.p, mask_q=sa.q,
                        leaf_id=leaf_id, weights=w_vec, codec=codec,
                        topology=topology, tree_groups=groups,
                        dp_sigma=dp_sigma_c, dp_seeds=dp_seeds,
                        dp_support_seed=dp_sup_seed)
                # ---- 3. fused scatter-add decode + dropout recovery ----
                elif topology == "tree":
                    dense = se.decode_leaf_tree(
                        streams_b, nb=1, m=size, size=size, splits=splits,
                        alive=alive if dropped else None,
                        pair_seeds=recovery_seeds if dropped else None,
                        pair_signs=pair_signs if dropped else None,
                        k_mask=k_mask, mask_p=sa.p, mask_q=sa.q,
                        leaf_id=leaf_id)
                else:
                    dense = se.decode_leaf_batch(
                        streams_b, nb=1, m=size, size=size,
                        alive=alive if dropped else None,
                        pair_seeds=recovery_seeds if dropped else None,
                        pair_signs=pair_signs if dropped else None,
                        k_mask=k_mask, mask_p=sa.p, mask_q=sa.q,
                        leaf_id=leaf_id)
                agg_leaves.append(
                    (dense / w_surv_total).reshape(shape)
                    .astype(leaf_dtypes[leaf_id]))
            with TraceAnnotation("fl.residuals"):
                # dropped clients transmitted nothing: their full accumulator
                # carries over as error feedback (nothing is lost, only
                # delayed)
                if dropped:
                    keep = alive.reshape((C,) + (1,) * len(shape))
                    new_res = jnp.where(
                        keep, new_res,
                        (r_st + d_st).astype(new_res.dtype))
                new_res_leaves.append(new_res)

        with TraceAnnotation("fl.residuals"):
            for ci, c in enumerate(participants):
                state.residuals[c] = jax.tree_util.tree_unflatten(
                    treedef, [nr[ci] for nr in new_res_leaves])

    with TraceAnnotation("fl.server_update"):
        if thgs is not None:
            agg = jax.tree_util.tree_unflatten(treedef, agg_leaves)
            # wire accounting: the gated self-pair slot (zero value at a
            # duplicated index) is not transmitted — k + (C-1)*k_mask slots
            # per leaf, matching the paper's Eq. 6 payload; leaf_sizes feed
            # the quantized codecs' exact packed-word sizes (core/codecs.py)
            rec = costs.round_record(
                state.round, model_size,
                [min(int(k), size) for k, size in zip(ks, leaf_sizes)],
                k_masks, n_clients=len(participants), bits=bits,
                n_survivors=len(survivors),
                threshold=proto.t if use_masks else 0,
                codec=codec, leaf_sizes=leaf_sizes,
                # facts-only DP fields: inactive parts stay at the 0.0
                # defaults so sigma=0/clip=inf records equal pre-DP records
                # bit for bit
                dp_clip=float(dp.clip) if dp_active and dp.clips else 0.0,
                dp_sigma=float(dp.sigma) if dp_active else 0.0,
                dp_delta=float(dp.delta) if dp_active and dp.noised else 0.0)
        else:
            # the dense baseline's aggregation runs here: there is no stream
            # encode or decode to span
            if codec != "f32":
                raise ValueError(
                    f"codec {codec!r} requires THGS sparse streams; dense "
                    "rounds have no stream wire to quantize (thgs is None)")
            deltas = {c: jax.tree_util.tree_map(lambda x: x[ci],
                                                deltas_stacked)
                      for ci, c in enumerate(participants)}
            if sa.enabled:
                from repro.core.secure_agg import dense_masked_update

                # dense Bonawitz has no sparse-support reconstruction: masks
                # are agreed among the survivors (the baseline's re-run
                # assumption)
                masked = []
                for c in survivors:
                    leaves_c = jax.tree_util.tree_leaves(deltas[c])
                    masked.append([
                        dense_masked_update(x, sa, c, survivors, state.round,
                                            i)
                        for i, x in enumerate(leaves_c)
                    ])
                summed = [
                    sum(m[i] for m in masked) / len(survivors)
                    for i in range(len(leaves))
                ]
                agg = jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(state.params),
                    [s.astype(d) for s, d in zip(summed, leaf_dtypes)],
                )
            else:
                agg = jax.tree_util.tree_map(
                    lambda *xs: sum(xs) / len(xs),
                    *[deltas[c] for c in survivors]
                )
            rec = costs.dense_round_record(
                state.round, model_size, n_clients=len(participants),
                bits=bits, n_survivors=len(survivors))

        for ci, c in enumerate(participants):
            state.losses[c] = losses_list[ci]
        state.params = jax.tree_util.tree_map(
            lambda p, d: p + fed.server_lr * d, state.params, agg
        )
        state.comm_log.append(rec)
        state.round += 1
    return state


# -------------------------------------------- async (FedBuff-style) updates
def staleness_weight(tau: int) -> float:
    """FedBuff's polynomial staleness discount ``(1 + tau)^(-1/2)``
    (Nguyen et al. 2022): a report trained on params ``tau`` server updates
    old contributes with this weight. ``tau == 0`` gives weight 1, so an
    all-fresh buffer reproduces the synchronous round exactly."""
    return (1.0 + float(tau)) ** -0.5


@partial(jax.jit, static_argnames=("loss_fn", "local_steps", "prox_mu"))
def batched_client_update_multi(
    params_stacked: PyTree,  # leading axis = reports (per-report stale params)
    batches_stacked: Any,    # leading axis = reports, then local_steps
    loss_fn: LossFn,
    local_steps: int,
    lr: float,
    prox_mu: float = 0.0,
) -> tuple[PyTree, jax.Array]:
    """Async twin of ``batched_client_update``: every report trains from its
    OWN (stale) parameter version, so params are vmapped alongside the
    batches instead of broadcast. Returns (deltas stacked [B, ...],
    losses [B])."""
    return jax.vmap(
        lambda p, b: _client_update(p, b, loss_fn, local_steps, lr, prox_mu)
    )(params_stacked, batches_stacked)


def run_async_update(
    state: FederatedState,
    client_batches: dict[int, Any],
    client_params: Mapping[int, PyTree],
    loss_fn: LossFn,
    fed: FedConfig,
    thgs: THGSConfig,
    bits: costs.BitModel = costs.PAPER_BITS,
    staleness: Mapping[int, int] | None = None,
    client_weights: Mapping[int, float] | None = None,
    codec: str = "f32",
    topology: str = "flat",
    tree_groups: int = 0,
) -> FederatedState:
    """One FedBuff-style buffered server update (DESIGN.md §13).

    The buffer holds one report per client in ``client_batches``: client
    ``c`` ran local SGD from the stale parameter version ``client_params[c]``
    (``staleness[c]`` server updates old) and its THGS-sparsified delta joins
    the aggregate with weight ``staleness_weight(tau) * client_weights[c]``.
    The server applies the weight-normalized aggregate exactly like a
    synchronous round — with ``staleness`` all zero this IS ``run_round``
    bit-exactly (tested in tests/test_async_sim.py).

    Secure aggregation is not supported in async mode: pair masks are agreed
    round-synchronously among a known cohort, which a streaming buffer breaks
    (SimConfig.validate rejects the combination). THGS is required — the
    async path exists to exercise the sparse-stream data plane. Clients in
    one buffer must be distinct: error-feedback residual write-back is
    per-client, and a duplicate's first report would be silently clobbered.
    """
    with TraceAnnotation("fl.local_sgd"):
        if thgs is None:
            raise ValueError("run_async_update requires THGS sparse streams")
        if topology not in ("flat", "tree"):
            raise ValueError(f"unknown topology {topology!r}")
        participants = sorted(client_batches.keys())
        B = len(participants)
        assert len(set(participants)) == B, "buffer clients must be distinct"
        staleness = staleness or {}
        taus = [int(staleness.get(c, 0)) for c in participants]
        w_list = [staleness_weight(t) *
                  (float(client_weights.get(c, 1.0)) if client_weights
                   else 1.0)
                  for c, t in zip(participants, taus)]
        w_vec = jnp.asarray(w_list, jnp.float32)
        w_total = float(sum(w_list))

        leaves, treedef = jax.tree_util.tree_flatten(state.params)
        leaf_shapes = [x.shape for x in leaves]
        leaf_dtypes = [x.dtype for x in leaves]
        leaf_sizes = [x.size for x in leaves]
        model_size = sum(leaf_sizes)

        # ---- 1. every report's local SGD from its own stale params ----
        batches_stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[client_batches[c] for c in participants])
        params_stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[client_params[c] for c in participants])
        deltas_stacked, losses = batched_client_update_multi(
            params_stacked, batches_stacked, loss_fn, fed.local_steps,
            fed.local_lr, fed.prox_mu if fed.algorithm == "fedprox" else 0.0)
    with TraceAnnotation("fl.host_read", values=B):
        losses_list = [float(x) for x in losses]

    with TraceAnnotation("fl.schedule"):
        loss_prev = _mean_or_none([state.losses.get(c) for c in participants])
        loss_curr = _mean_or_none(losses_list)
        ks = schedules.leaf_ks(
            thgs, leaf_sizes, t=state.round,
            total_rounds=fed.rounds, loss_prev=loss_prev,
            loss_curr=loss_curr)

    with TraceAnnotation("fl.restack"):
        groups = tree_groups if tree_groups > 0 else max(
            2, int(round(B ** 0.5)))
        delta_leaves = jax.tree_util.tree_leaves(deltas_stacked)
        res_per_client = [jax.tree_util.tree_leaves(state.residuals[c])
                          for c in participants]
        res_stacked = [jnp.stack([rl[i] for rl in res_per_client])
                       for i in range(len(leaves))]

    agg_leaves, new_res_leaves = [], []
    for leaf_id, (d_st, r_st, k, size, shape) in enumerate(
            zip(delta_leaves, res_stacked, ks, leaf_sizes, leaf_shapes)):
        # ---- 2. batched unified-stream encode, staleness-weighted ----
        with TraceAnnotation("fl.encode", leaf=leaf_id):
            streams_b, new_res = se.encode_leaf_batch(
                d_st, r_st, k=k, nb=1, m=size, size=size,
                selector=thgs.selector, sample_frac=thgs.sample_frac,
                leaf_id=leaf_id, weights=w_vec, codec=codec)
        # ---- 3. fused decode (flat or hierarchical) ----
        splits = se.tree_splits(size, groups) if topology == "tree" else ()
        with TraceAnnotation(
                "fl.decode", leaf=leaf_id,
                scatter_steps=se.scatter_steps(B, k, 0, 1, size,
                                               splits=splits)):
            if topology == "tree":
                dense = se.decode_leaf_tree(
                    streams_b, nb=1, m=size, size=size, splits=splits)
            else:
                dense = se.decode_leaf_batch(streams_b, nb=1, m=size,
                                             size=size)
            agg_leaves.append(
                (dense / w_total).reshape(shape).astype(leaf_dtypes[leaf_id]))
        with TraceAnnotation("fl.residuals"):
            new_res_leaves.append(new_res)

    with TraceAnnotation("fl.residuals"):
        for ci, c in enumerate(participants):
            state.residuals[c] = jax.tree_util.tree_unflatten(
                treedef, [nr[ci] for nr in new_res_leaves])
    with TraceAnnotation("fl.server_update"):
        agg = jax.tree_util.tree_unflatten(treedef, agg_leaves)
        for ci, c in enumerate(participants):
            state.losses[c] = losses_list[ci]
        rec = costs.round_record(
            state.round, model_size,
            [min(int(k), size) for k, size in zip(ks, leaf_sizes)],
            [0] * len(ks), n_clients=B, bits=bits, n_survivors=B,
            threshold=0, codec=codec, leaf_sizes=leaf_sizes,
            staleness=tuple(taus))
        state.params = jax.tree_util.tree_map(
            lambda p, d: p + fed.server_lr * d, state.params, agg)
        state.comm_log.append(rec)
        state.round += 1
    return state
