"""Datacenter train steps: dense FedAvg baseline + THGS/secure-agg federated step.

Two step builders (DESIGN.md §2, §6):

  * ``make_dense_train_step`` — the conventional-FL / data-parallel baseline:
    grads reduce densely over every batch axis (what FedAvg's server sum costs).

  * ``make_fl_train_step`` — the paper's technique as the collective schedule:
    shard_map over the federation axis ('pod' on the multi-pod mesh, 'data'
    otherwise); each participant computes its local update, encodes it with
    the unified stream engine (core/streams.py via core/blocked.py — block-
    local THGS top-k + sparse pairwise masks, DESIGN.md §3), and the
    cross-participant exchange is an all_gather of the small static streams +
    scatter-add — instead of a dense psum. The federation axis is excluded from
    fsdp so every participant owns a full logical model copy.

Training uses plain SGD (the paper's client optimizer); AdamW is available for
the non-FL baseline via ``optimizer=``.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import schedules
from repro.core import streams as se
from repro.core.blocked import decode_blocked_sum, encode_leaf_blocked
from repro.core.types import SecureAggConfig, THGSConfig
from repro.launch import shardings as shd
from repro.models import transformer as tf

PyTree = Any


def _shard_map(f, *, mesh, in_specs, out_specs, manual_axes):
    """Partial-manual shard_map: manual only over ``manual_axes``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=set(manual_axes))


def loss_fn(params: PyTree, cfg: ArchConfig, batch: dict) -> jax.Array:
    return tf.train_loss(params, cfg, batch)


# --------------------------------------------------------------------- dense
def make_dense_train_step(cfg: ArchConfig, lr: float = 0.01,
                          n_micro: int = 1) -> Callable:
    """SGD train step; n_micro > 1 accumulates gradients over microbatches
    (lax.scan over batch splits) — the standard way to fit large models'
    activation footprint on fixed HBM."""

    def step(params: PyTree, batch: dict):
        if n_micro == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, cfg, batch)
        else:
            micro = jax.tree_util.tree_map(
                lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                    *x.shape[1:]), batch)

            def acc_fn(carry, mb):
                loss_a, gacc = carry
                loss, g = jax.value_and_grad(loss_fn)(params, cfg, mb)
                gacc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gacc, g)
                return (loss_a + loss, gacc), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                acc_fn, (jnp.zeros(()), zeros), micro)
            loss = loss / n_micro
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
        new_params = jax.tree_util.tree_map(
            lambda p, g: (p.astype(jnp.float32) - lr * g.astype(jnp.float32)
                          ).astype(p.dtype),
            params, grads)
        return new_params, loss

    return step


# ------------------------------------------------------------------ federated
def fl_leaf_plan(params_shape: PyTree, thgs: THGSConfig, n_blocks: int):
    """Static per-leaf (k_block, n_blocks) from the Eq. 1 hierarchical schedule."""
    leaves = jax.tree_util.tree_leaves(params_shape)
    sizes = [leaf.size for leaf in leaves]
    ks = schedules.leaf_ks(thgs, sizes)
    plan = []
    for size, k in zip(sizes, ks):
        from repro.core.blocked import block_layout

        nb, m, _ = block_layout(size, n_blocks)
        plan.append((max(1, -(-k // nb)), nb))
    return plan


def make_fl_train_step_v2(
    cfg: ArchConfig,
    mesh,
    fed_axis: str,
    thgs: THGSConfig,
    sa: SecureAggConfig,
    lr: float = 0.01,
    server_lr: float = 1.0,
    n_micro: int = 1,
) -> Callable:
    """FL step, GSPMD-first variant (the production default).

    shard_map (partial-manual over the federation axis) wraps ONLY the per-
    participant gradient computation — the one thing GSPMD cannot express.
    Everything else (THGS blocked encode, mask generation, the sparse
    exchange, the server update) runs in plain GSPMD on pod-stacked tensors,
    where (a) the partitioner is robust and (b) the sharding-aligned block
    view makes the whole encode zero-communication. The cross-participant
    exchange is the scatter-add of the pod-sharded streams into a pod-
    replicated dense buffer — GSPMD lowers it to an all-gather of exactly the
    sparse streams (the paper's communication claim, visible in the HLO).
    """
    from repro.core.blocked import block_layout, sharding_aligned_transform
    from repro.launch.mesh import logical_rules

    n_fed = dict(zip(mesh.axis_names, mesh.devices.shape))[fed_axis]
    rules = logical_rules(mesh, fed_axis=fed_axis)
    intra_axes = tuple(a for a in mesh.axis_names if a != fed_axis)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def step(params, residuals, batch, round_key):
        params_shape = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        pspecs = jax.tree_util.tree_leaves(
            shd.param_specs(params_shape, rules, mesh),
            is_leaf=lambda x: isinstance(x, P))
        sizes = [x.size for x in jax.tree_util.tree_leaves(params_shape)]
        leaf_k = schedules.leaf_ks(thgs, sizes)

        # ---- per-participant grads (the only manual-region piece) ----
        @functools.partial(
            _shard_map, mesh=mesh,
            in_specs=(P(), P(fed_axis)),
            out_specs=(P(fed_axis), P(fed_axis)),
            manual_axes=(fed_axis,))
        def per_pod_grads(p, b):
            if n_micro == 1:
                loss, grads = jax.value_and_grad(loss_fn)(p, cfg, b)
            else:
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                        *x.shape[1:]), b)

                def acc_fn(carry, mb):
                    l_a, gacc = carry
                    l, gm = jax.value_and_grad(loss_fn)(p, cfg, mb)
                    gacc = jax.tree_util.tree_map(
                        lambda a2, b2: a2 + b2.astype(jnp.float32), gacc, gm)
                    return (l_a + l, gacc), None

                zeros = jax.tree_util.tree_map(
                    lambda q: jnp.zeros(q.shape, jnp.float32), p)
                (loss, grads), _ = jax.lax.scan(
                    acc_fn, (jnp.zeros(()), zeros), micro)
                loss = loss / n_micro
                grads = jax.tree_util.tree_map(lambda g2: g2 / n_micro, grads)
            grads = jax.tree_util.tree_map(
                lambda g2: g2[None].astype(jnp.bfloat16), grads)
            return grads, loss[None]

        grads_stacked, losses = per_pod_grads(params, batch)
        # pin the stacked grads to (fed, param-layout) before the encode —
        # the shard_map exit leaves the intra-participant axes unspecified
        # (observed: replicated-within-pod grads, 2x step memory)
        g_leaves = [
            jax.lax.with_sharding_constraint(
                g2, NamedSharding(mesh, P(fed_axis, *gs)))
            for g2, gs in zip(jax.tree_util.tree_leaves(grads_stacked),
                              pspecs)]
        r_leaves = jax.tree_util.tree_leaves(residuals)
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        new_params, new_res = [], []
        for leaf_id, (gs, rs, pl, gspec) in enumerate(
                zip(g_leaves, r_leaves, p_leaves, pspecs)):
            shape = pl.shape
            tr = sharding_aligned_transform(shape, gspec, axis_sizes,
                                            intra_axes)
            if os.environ.get("REPRO_FL_V2_GENERIC", "0") == "1":
                tr = None
            if tr is not None:
                to_b, from_b, nb, m, front = tr
            else:
                n_intra = 1
                for a in intra_axes:
                    n_intra *= axis_sizes[a]
                nb, m, padded = block_layout(pl.size, n_intra)
                size0 = pl.size
                to_b = (lambda x, _p=padded, _nb=nb, _m=m, _s=size0:
                        jnp.pad(x.reshape(-1), (0, _p - _s)).reshape(_nb, _m))
                from_b = (lambda b2, _s=size0, _sh=shape:
                          b2.reshape(-1)[:_s].reshape(_sh))
                front = intra_axes if nb == n_intra else ()
            kb = max(1, min(m, -(-leaf_k[leaf_id] // nb)))
            stacked_spec = P(fed_axis, front if front else None, None)

            acc = (jax.vmap(to_b)(rs.astype(jnp.float32))
                   + jax.vmap(to_b)(-lr * gs.astype(jnp.float32)))
            acc = jax.lax.with_sharding_constraint(
                acc, NamedSharding(mesh, stacked_spec))  # [n_fed, nb, m]

            # ---- batched unified-stream encode: all pods in one vmapped
            # program (core/streams.py is the single implementation; pair
            # keys are the fold_in chain both endpoints can derive) ----
            k_mask = (max(1, int(pl.size * sa.mask_ratio / n_fed / nb))
                      if (sa.enabled and n_fed >= 2) else 0)
            if k_mask > 0:
                mkey = jax.random.fold_in(round_key, leaf_id)
                pair_keys, pair_signs = se.fold_pair_key_matrix(mkey, n_fed)
            else:
                pair_keys = pair_signs = None
            streams_b, new_blocks = se.encode_batch_blocks(
                acc, kb, pair_keys=pair_keys, pair_signs=pair_signs,
                k_mask=k_mask, mask_p=sa.p, mask_q=sa.q)
            nr = jax.vmap(from_b)(new_blocks).astype(rs.dtype)
            new_res.append(jax.lax.with_sharding_constraint(
                nr, NamedSharding(mesh, P(fed_axis, *gspec))))

            # ---- the sparse federation exchange: pod-sharded streams ->
            # pod-replicated dense sum (GSPMD: all-gathers only the streams)
            gidx = streams_b.indices              # [n_fed, nb, ktot] global
            dense = jnp.zeros((nb, m), jnp.float32)
            dense = jax.lax.with_sharding_constraint(
                dense, NamedSharding(mesh, P(front if front else None, None)))
            dense = dense.at[gidx // m, gidx % m].add(
                streams_b.values / n_fed)
            agg = from_b(dense).astype(jnp.float32)
            agg = jax.lax.with_sharding_constraint(
                agg, NamedSharding(mesh, gspec))
            new_params.append(
                (pl.astype(jnp.float32) + server_lr * agg).astype(pl.dtype))

        new_params = jax.tree_util.tree_unflatten(treedef, new_params)
        new_res = jax.tree_util.tree_unflatten(treedef, new_res)
        return new_params, new_res, jnp.mean(losses)

    return step



def make_fl_train_step(
    cfg: ArchConfig,
    mesh,
    fed_axis: str,
    thgs: THGSConfig,
    sa: SecureAggConfig,
    lr: float = 0.01,
    server_lr: float = 1.0,
    n_micro: int = 1,
) -> Callable:
    """Returns step(params, residuals, batch, round_key) -> (params, residuals, loss).

    residuals live per-participant: leading dim n_fed, manually sharded over the
    federation axis.
    """
    n_fed = dict(zip(mesh.axis_names, mesh.devices.shape))[fed_axis]
    n_devices = mesh.devices.size
    n_blocks = n_devices // n_fed  # one block per device within a participant

    from repro.launch.mesh import logical_rules

    rules = logical_rules(mesh, fed_axis=fed_axis)
    intra_axes = tuple(a for a in mesh.axis_names if a != fed_axis)

    def step(params, residuals, batch, round_key):
        params_shape = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        plan = fl_leaf_plan(params_shape, thgs, n_blocks)
        grad_specs = jax.tree_util.tree_leaves(
            shd.param_specs(params_shape, rules, mesh),
            is_leaf=lambda x: isinstance(x, P))
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        from repro.core.blocked import sharding_aligned_transform
        # §Perf note: the zero-communication sharding-aligned block view is
        # gated OFF by default — XLA's partial-manual SPMD partitioner cannot
        # form federation peer groups for the transposed view (hard CHECK,
        # tracked upstream as the Shardy migration b/433785288). Enable with
        # REPRO_FL_ALIGNED_BLOCKS=1 once the Shardy partitioner lands.
        use_aligned = os.environ.get("REPRO_FL_ALIGNED_BLOCKS", "0") == "1"
        transforms = [
            (sharding_aligned_transform(leaf.shape, gs, axis_sizes, intra_axes)
             if use_aligned else None)
            for (leaf, gs) in zip(
                jax.tree_util.tree_leaves(params_shape), grad_specs)]
        # per-leaf k_block re-derived for the transform's block count
        from repro.core import schedules as _sched
        sizes = [x.size for x in jax.tree_util.tree_leaves(params_shape)]
        leaf_k = _sched.leaf_ks(thgs, sizes)
        leaf_names = [
            next((str(getattr(q, "key", "")) for q in reversed(path)), "")
            for path, _ in jax.tree_util.tree_flatten_with_path(
                params_shape)[0]]

        @functools.partial(
            _shard_map,
            mesh=mesh,
            in_specs=(P(), P(fed_axis), P(fed_axis), P()),
            out_specs=(P(), P(fed_axis), P(fed_axis)),
            manual_axes=(fed_axis,),
        )
        def fed_step(p, res, b, key):
            # inside: manual over fed_axis; data/model axes still GSPMD-auto.
            # residuals carry an explicit per-participant leading dim (1 here);
            # the batch is just this participant's slice along dim 0.
            res = jax.tree_util.tree_map(lambda x: x[0], res)
            self_id = jax.lax.axis_index(fed_axis)

            if n_micro == 1:
                loss, grads = jax.value_and_grad(loss_fn)(p, cfg, b)
            else:
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape(n_micro, x.shape[0] // n_micro,
                                        *x.shape[1:]), b)

                def acc_fn(carry, mb):
                    l_a, gacc = carry
                    l, gm = jax.value_and_grad(loss_fn)(p, cfg, mb)
                    gacc = jax.tree_util.tree_map(
                        lambda a2, b2: a2 + b2.astype(jnp.float32), gacc, gm)
                    return (l_a + l, gacc), None

                zeros = jax.tree_util.tree_map(
                    lambda q: jnp.zeros(q.shape, jnp.float32), p)
                (loss, grads), _ = jax.lax.scan(
                    acc_fn, (jnp.zeros(()), zeros), micro)
                loss = loss / n_micro
                grads = jax.tree_util.tree_map(lambda g2: g2 / n_micro, grads)
            # local update = -lr * grad  (one local FedSGD step)
            leaves, treedef = jax.tree_util.tree_flatten(
                jax.tree_util.tree_map(lambda g: -lr * g, grads))
            res_leaves = jax.tree_util.tree_leaves(res)

            # replicate the small streams within the participant before the
            # cross-participant gather ("gather to leader, then exchange"):
            # XLA's partial-manual partitioner cannot form pod-peer groups
            # for tensors still sharded over the auto axes (hard CHECK).
            replicate = (
                os.environ.get("REPRO_FL_STREAM_REPLICATE", "1") == "1")

            def exchange(stream, nb2, size2, bshard2, tr2):
                # sparse federation exchange for one (sub-)leaf
                g = se.gather_streams(stream, fed_axis, replicate=replicate)
                return decode_blocked_sum(
                    g.indices, g.values, size2, nb2, weight=1.0 / n_fed,
                    block_sharding=bshard2, transform=tr2)

            new_res, agg_leaves = [], []
            for leaf_id, (g, r, (kb, nb)) in enumerate(
                    zip(leaves, res_leaves, plan)):
                tr = transforms[leaf_id]
                if tr is not None:
                    nb = tr[2]
                    kb = max(1, -(-leaf_k[leaf_id] // nb))
                # normalize the embedding grad's sharding to the param layout
                # first — the scatter-produced cotangent otherwise reaches the
                # blocked encode with a layout the partial-manual partitioner
                # cannot form federation peer groups for (hard XLA CHECK).
                # Constraining every leaf trips the same CHECK on small meshes,
                # so only the scatter-produced leaf is normalized.
                if leaf_names[leaf_id] == "embed":
                    g = jax.lax.with_sharding_constraint(
                        g, grad_specs[leaf_id])
                k_mask_block = 0
                mask_key = None
                if sa.enabled and n_fed >= 2:
                    k_mask_block = max(
                        1, int(g.size * sa.mask_ratio / n_fed / nb))
                    mask_key = jax.random.fold_in(key, leaf_id)
                try:  # blocks align with this leaf's sharded axes
                    am = jax.sharding.get_abstract_mesh()
                    axes = tr[4] if tr is not None else intra_axes
                    bshard = NamedSharding(am, P(axes, None))
                except Exception:
                    bshard = None

                # Large stacked leaves: scan the encode+exchange over the
                # leading (layer) dim — the pad/reshape to the block view
                # replicates ONE slice, not the whole multi-GiB leaf
                # (measured: granite-20b FL train 172 GiB -> per-layer-slice).
                # flatten the stacked UNSHARDED leading dims into the scan
                # axis (merging a sharded dim into the scan axis would force
                # GSPMD to replicate the whole leaf — observed 150 GiB on the
                # llama4 expert tensors); chunk huge 2D leaves the same way
                spec_entries = tuple(grad_specs[leaf_id]) + (None,) * g.ndim
                if g.ndim >= 3:
                    lead = 1
                    n_lead_dims = 0
                    for di, d in enumerate(g.shape[:-2]):
                        if spec_entries[di] is not None:
                            break
                        lead *= d
                        n_lead_dims += 1
                    slice_shape = g.shape[n_lead_dims:]
                elif g.ndim == 2 and g.size >= 1 << 28 and g.shape[0] % 16 == 0 \
                        and spec_entries[0] is None:
                    lead, slice_shape = 16, (g.shape[0] // 16, g.shape[1])
                else:
                    lead, slice_shape = 0, None
                if (tr is None and lead > 1
                        and g.size // lead >= 1 << 20):
                    g = g.reshape(lead, *slice_shape)
                    r = r.reshape(lead, *slice_shape)
                    kb_s = max(1, -(-leaf_k[leaf_id] // (nb * lead)))
                    km_s = (max(1, k_mask_block // lead)
                            if k_mask_block else 0)

                    def slice_body(i, gr, _kb=kb_s, _km=km_s, _nb=nb,
                                   _lid=leaf_id, _bs=bshard):
                        gi, ri = gr
                        mk = (jax.random.fold_in(
                            jax.random.fold_in(key, _lid), i)
                            if _km else None)
                        st, rn = encode_leaf_blocked(
                            gi, ri, _kb, _nb,
                            mask_key=mk, k_mask_block=_km,
                            n_peers=n_fed, self_id=self_id,
                            mask_lo=sa.p, mask_q=sa.q, block_sharding=_bs)
                        dense = exchange(st, _nb, gi.size, _bs, None)
                        return dense.reshape(gi.shape), rn

                    def scan_fn(i, gr):
                        out = slice_body(i, gr)
                        return i + 1, out

                    _, (agg_sl, res_sl) = jax.lax.scan(
                        scan_fn, jnp.int32(0), (g, r))
                    orig_shape = leaves[leaf_id].shape
                    new_res.append(
                        res_sl.reshape(orig_shape).astype(r.dtype))
                    agg_leaves.append(
                        agg_sl.reshape(orig_shape).astype(g.dtype))
                    continue

                stream, r_new = encode_leaf_blocked(
                    g, r, kb, nb,
                    mask_key=mask_key, k_mask_block=k_mask_block,
                    n_peers=n_fed, self_id=self_id,
                    mask_lo=sa.p, mask_q=sa.q, block_sharding=bshard,
                    transform=tr)
                new_res.append(r_new)
                # ---- the sparse federation exchange (vs dense psum) ----
                dense = exchange(stream, nb, g.size, bshard, tr)
                agg = (dense if tr is not None
                       else dense.reshape(g.shape)).astype(g.dtype)
                if tr is None:
                    try:  # back to the param layout for the update
                        agg = jax.lax.with_sharding_constraint(
                            agg, NamedSharding(
                                jax.sharding.get_abstract_mesh(),
                                grad_specs[leaf_id]))
                    except Exception:
                        pass
                agg_leaves.append(agg)

            agg = jax.tree_util.tree_unflatten(treedef, agg_leaves)
            new_p = jax.tree_util.tree_map(
                lambda pi, d: (pi.astype(jnp.float32) +
                               server_lr * d.astype(jnp.float32)
                               ).astype(pi.dtype), p, agg)
            new_res = jax.tree_util.tree_unflatten(treedef, new_res)
            # restore leading fed dim for the per-participant state
            new_res = jax.tree_util.tree_map(lambda x: x[None], new_res)
            return new_p, new_res, loss[None]

        new_params, new_res, losses = fed_step(params, residuals, batch,
                                               round_key)
        return new_params, new_res, jnp.mean(losses)

    return step


def init_fl_residuals(params_shape: PyTree, n_fed: int) -> PyTree:
    """ShapeDtypeStructs for the per-participant residual state (bf16)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((n_fed,) + x.shape, jnp.bfloat16),
        params_shape)
