"""The unified sparse-stream engine (paper Alg. 1/2, Eq. 5) — batched + jitted.

This module is the ONE implementation of the THGS ``top-k ∪ mask-support``
unified-stream encode and of the server-side scatter-add decode (DESIGN.md §3).
Every consumer — the single-host server (core/fedavg.py via core/secure_agg.py),
both datacenter step builders (launch/train.py), the blocked helpers
(core/blocked.py) and the examples — delegates here.

Data model
----------
A *stream* for one leaf is a static-shape pair ``(indices, values)``:

    indices : int32[..., n_blocks, k_total]   global indices row*m + col into the
                                              padded [n_blocks, m] block view
    values  : f32  [..., n_blocks, k_total]   w·acc[idx]·first_occurrence + mask

with a leading client axis when batched. ``n_blocks == 1, m == size`` recovers
the flat per-leaf stream of the paper's single-host protocol; ``n_blocks > 1``
is the device-aligned blocked layout of the datacenter path (core/blocked.py).

Encode is ``vmap``'d over the client axis and ``jit``'d end-to-end: one XLA
program encodes *all* clients of a round, replacing the per-client Python loop
of the seed implementation. Decode flattens every client's (weighted, liveness-
gated) stream into one index/value vector and scatter-adds it in a single pass
over the dense buffer — on TPU through the fused Pallas kernel
(kernels/stream_decode.py), elsewhere through XLA's native scatter.

Secure-aggregation semantics
----------------------------
Pairwise masks follow core/masks.py exactly. The default data plane is
**counter-based**: per-pair uint32 seeds (``pair_seed_matrix``, DH-derived in
masks.py / reconstructed via Shamir shares in repro/secagg) drive the murmur
streams of ``kernels/ref.pair_mask_stream_ref`` — Pallas twin
``kernels/mask_prng.pair_mask_streams`` on TPU — generating every client's
pair masks for a leaf in ONE fused pass (``mask_streams_all_pairs``), instead
of the per-pair host loop of the seed implementation. The legacy jax.random
path (``pair_key_matrix``/``pairwise_mask_rows``) remains for the in-trace
fold-key variants the datacenter shard_map step uses. Client weights are
applied to the *gradient* part of the values only — client-side, before
masking — so non-uniform weighted aggregation keeps mask cancellation exact
(server-side weighting would scale each endpoint's mask differently).
Dropout recovery is Bonawitz-style: the server regenerates every
survivor→dropped pair mask — from Shamir-reconstructed seeds
(``dropout_cancel_streams_seeded``, the repro/secagg protocol path) or from
the legacy pair keys (``dropout_cancel_streams``) — and subtracts it, so the
aggregate over survivors equals the unmasked weighted sparse sum.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# the codec x secagg rejection lives in ONE place (repro.lint RPL003)
from repro.core.codecs import reject_codec_with_masks

# The mesh axis name the client-parallel round shards over. Defined here (not
# in launch/mesh.py) because core must not import launch; the mesh builders in
# launch/mesh.py import this constant.
CLIENT_AXIS = "clients"


class StreamBatch(NamedTuple):
    """Stacked unified streams: leading axis = clients (absent when single)."""

    indices: jax.Array  # int32[..., n_blocks, k_total]
    values: jax.Array   # f32  [..., n_blocks, k_total]

    @property
    def k_total(self) -> int:
        return self.indices.shape[-1]


# --------------------------------------------------------------------- layout
def block_layout(size: int, n_blocks: int) -> tuple[int, int, int]:
    """(n_blocks, block_len, padded) — small leaves collapse to one block."""
    if size < 4 * n_blocks:
        n_blocks = 1
    m = -(-size // n_blocks)
    return n_blocks, m, n_blocks * m


def to_blocks(x: jax.Array, n_blocks: int, m: int) -> jax.Array:
    """Flat/leaf tensor -> padded [n_blocks, m] row-major block view."""
    flat = x.reshape(-1)
    pad = n_blocks * m - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(n_blocks, m)


def from_blocks(blocks: jax.Array, size: int, shape: tuple) -> jax.Array:
    return blocks.reshape(-1)[:size].reshape(shape)


# ------------------------------------------------------- first-occurrence gate
def first_occurrence_rows(idx: jax.Array) -> jax.Array:
    """Per-row boolean: True iff the slot is the first occurrence of its index.

    Sort-based O(k log k) per row; duplicates of an index occupy consecutive
    ranks after sorting, so a slot is first iff its sorted predecessor differs.
    """
    order = jnp.argsort(idx, axis=-1)
    sorted_idx = jnp.take_along_axis(idx, order, -1)
    is_first = jnp.concatenate(
        [jnp.ones_like(sorted_idx[..., :1], bool),
         sorted_idx[..., 1:] != sorted_idx[..., :-1]], -1)
    out = jnp.zeros_like(is_first)
    rows = jnp.arange(idx.shape[0])[:, None]
    return out.at[rows, order].set(is_first)


# ------------------------------------------------------------- selector stage
def select_topk_rows(acc: jax.Array, k: int, selector: str,
                     sample_frac: float) -> jax.Array:
    """[n_blocks, m] -> int32[n_blocks, k] per-row top-|.| indices."""
    abs_acc = jnp.abs(acc)
    if selector == "sampled":
        from repro.core.sparsify import _sampled_topk

        _, idx = jax.vmap(lambda r: _sampled_topk(r, k, sample_frac))(abs_acc)
    else:  # 'exact' and 'local' (the caller pre-blocks for 'local')
        _, idx = jax.lax.top_k(abs_acc, k)
    return idx.astype(jnp.int32)


# ----------------------------------------------------- THE unified-stream core
def unified_stream_rows(
    acc: jax.Array,            # f32[n_blocks, m] error-feedback accumulator
    k: int,
    mask_idx: jax.Array | None,    # int32[n_blocks, k_mask_total] | None
    mask_vals: jax.Array | None,   # f32  [n_blocks, k_mask_total] | None
    *,
    selector: str = "exact",
    sample_frac: float = 0.01,
    weight: jax.Array | float = 1.0,
    dp_support: jax.Array | None = None,  # int32[n_blocks, k] public support
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One client, one leaf: ``top-k(|acc|) ∪ support(mask)`` unified stream.

    This is the single implementation of the paper's Eq. 5 encode (Alg. 2
    lines 10-17). Returns ``(idx, vals, new_acc)`` where ``idx`` is the local
    per-row column index, ``vals = weight·acc[idx]·first_occurrence + mask``
    (duplicate indices transmit the gradient once; mask values ride in their
    dedicated slots), and ``new_acc`` zeroes every transmitted position —
    including mask-support positions below the top-k threshold.

    ``dp_support`` switches the stream into its DP release shape (core/dp.py,
    DESIGN.md §15): the k data slots release the *public common support*
    instead of the data-dependent top-k (the transmitted indices leak
    nothing), mask slots carry masks ONLY (no gradient values ride them),
    and ``new_acc`` zeroes only the released support positions — everything
    else stays in the error-feedback residual.
    """
    nb, m = acc.shape
    k = int(min(k, m))
    if dp_support is not None:
        idx_t = dp_support
    else:
        idx_t = select_topk_rows(acc, k, selector, sample_frac)
    if mask_idx is not None and mask_idx.shape[-1] > 0:
        idx = jnp.concatenate([idx_t, mask_idx], -1)
        mvals = jnp.concatenate(
            [jnp.zeros((nb, k), jnp.float32), mask_vals], -1)
    else:
        idx = idx_t
        mvals = jnp.zeros((nb, k), jnp.float32)

    first = first_occurrence_rows(idx)
    if dp_support is not None:
        # DP: gradient values are released on the support slots alone; a mask
        # slot that happens to be the first occurrence of its index must not
        # smuggle the (un-noised) gradient value out beside the masks
        data_slot = jnp.concatenate(
            [jnp.ones((nb, k), bool),
             jnp.zeros((nb, idx.shape[-1] - k), bool)], -1)
        first = first & data_slot
    gvals = jnp.take_along_axis(acc, idx, -1)
    vals = weight * gvals * first.astype(acc.dtype) + mvals
    rows = jnp.arange(nb)[:, None]
    new_acc = acc.at[rows, idx_t if dp_support is not None else idx].set(0.0)
    return idx, vals, new_acc


# ------------------------------------------------------------- pairwise masks
def pair_key_matrix(sa, participant_ids: Sequence[int], round_t: int):
    """Host-side [C, C] pair keys + signs from the DH-agreed pair secrets.

    ``keys[i, j]`` is ``masks.pair_key(sa, ids[i], ids[j], round_t)`` (folded
    with the leaf id inside the encode); ``signs[i, j]`` is +1 when
    ids[i] < ids[j], -1 when >, and 0 on the diagonal (self pair inactive).
    Both endpoints of a pair hold identical keys, so the generated masks cancel
    in the aggregate — and the server can regenerate them for dropout recovery.
    """
    from repro.core.masks import pair_key

    ids = list(participant_ids)
    n = len(ids)
    keys = [[pair_key(sa, ids[i], ids[j], round_t)
             for j in range(n)] for i in range(n)]
    keys = jnp.stack([jnp.stack(row) for row in keys])
    signs = jnp.array(
        [[0.0 if i == j else (1.0 if ids[i] < ids[j] else -1.0)
          for j in range(n)] for i in range(n)], jnp.float32)
    return keys, signs


def pair_seed_matrix(sa, participant_ids: Sequence[int], round_t: int):
    """Host-side [C, C] uint32 counter seeds + signs for the round's pairs.

    ``seeds[i, j]`` is ``masks.pair_seed(sa, ids[i], ids[j], round_t)`` — the
    DH-agreed pair secret hashed with the round, identical from both ends, so
    the counter-based mask streams cancel in the aggregate. The diagonal
    (self pair) is seed 0 with sign 0; its slots are value-gated to zero and
    support-gated onto the block's top-1 index by the encode. This is what
    the repro/secagg round protocol hands the data plane; the server re-derives
    exactly these seeds for dropped clients from their Shamir shares.
    """
    from repro.core import masks

    ids = list(participant_ids)
    # one key derivation per participant and one modexp per unordered pair
    # (the seed is symmetric), not per matrix entry — at paper-scale cohorts
    # the per-entry sha256+modexp re-derivation dominates round setup
    privs = [masks.dh_private(sa.seed, u) for u in ids]
    pubs = [masks.dh_public(x) for x in privs]
    return masks.seed_matrix_from_keys(ids, privs, pubs, round_t)


def _fold_seeds(seeds: jax.Array, leaf_id) -> jax.Array:
    from repro.kernels import ref as kref

    seeds = jnp.asarray(seeds, jnp.uint32)
    return kref.fold_leaf_seed(seeds, leaf_id) if leaf_id is not None \
        else seeds


def _client_mask_layout(idx: jax.Array, mag: jax.Array, signs: jax.Array,
                        nb: int, k_mask: int) -> tuple[jax.Array, jax.Array]:
    """``[Cr, C, nb, k_mask]`` pair streams -> the engine's per-client layout
    ``[Cr, nb, C * k_mask]`` (peer-major within a row), signs applied to the
    magnitudes. Shared by the full-matrix and row-slice generators so the
    serial and sharded encodes can never disagree on the slot layout."""
    cr, n = idx.shape[:2]
    vals = jnp.asarray(signs, jnp.float32)[:, :, None, None] * mag
    idx = idx.transpose(0, 2, 1, 3)
    vals = vals.transpose(0, 2, 1, 3)
    return idx.reshape(cr, nb, n * k_mask), vals.reshape(cr, nb, n * k_mask)


def mask_streams_all_pairs(
    pair_seeds: jax.Array,   # uint32[C, C] counter seeds (0 on the diagonal)
    pair_signs: jax.Array,   # f32[C, C] Bonawitz signs (0 on the diagonal)
    nb: int,
    k_mask: int,
    m: int,
    *,
    p: float,
    q: float,
    leaf_id: int | jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Every client's concatenated pair-mask streams in ONE fused pass.

    Counter-based data plane: all C*C pair streams are generated by a single
    kernel/oracle dispatch (kernels/ops.pair_mask_streams) and reshaped to the
    engine's per-client layout ``[C, nb, C * k_mask]`` (peer-major within a
    row, self slot included — the encode gates it). Replaces the per-pair
    host loop of masks.client_masks on the batched path.
    """
    from repro.kernels import ops

    C = pair_seeds.shape[0]
    seeds = _fold_seeds(pair_seeds, leaf_id)
    # the seed matrix is symmetric and a stream's idx/|val| depend only on
    # the seed, so generate each unordered pair (upper triangle incl. the
    # diagonal) once and mirror via a static gather — halving the mask-PRNG
    # work of the per-leaf hot path. Signs are applied outside the
    # generator (sign * (p + q*u), exact for sign in {-1, 0, +1}), so the
    # mirrored copy is the bit-exact negation the cancellation needs.
    iu, ju = np.triu_indices(C)
    tri = np.zeros((C, C), np.int64)
    tri[iu, ju] = np.arange(len(iu))
    tri[ju, iu] = tri[iu, ju]
    idx_u, mag_u = ops.pair_mask_streams(
        seeds[iu, ju], jnp.ones((len(iu),), jnp.float32),
        nb=nb, k_mask=k_mask, m=m, p=p, q=q)
    return _client_mask_layout(idx_u[tri], mag_u[tri], pair_signs, nb, k_mask)


def mask_streams_rows(
    seeds_rows: jax.Array,   # uint32[C_loc, C] this shard's rows of the matrix
    signs_rows: jax.Array,   # f32[C_loc, C] matching sign rows
    nb: int,
    k_mask: int,
    m: int,
    *,
    p: float,
    q: float,
    leaf_id: int | jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """A row-slice of ``mask_streams_all_pairs`` for the client-sharded round.

    Inside the shard_map each device holds ``C_loc = C / n_dev`` clients and
    generates only their pair-mask streams from the corresponding rows of the
    (replicated) seed/sign matrices. A stream's idx/|val| depend only on the
    seed and the seed matrix is symmetric, so row-wise generation is bit-exact
    with the triangle-mirrored full-matrix pass the serial path uses — the
    parity tests pin this. Returns the engine's per-client layout
    ``(idx int32[C_loc, nb, C*k_mask], vals f32[C_loc, nb, C*k_mask])``.
    """
    from repro.kernels import ops

    c_loc, n = seeds_rows.shape
    seeds = _fold_seeds(seeds_rows, leaf_id).reshape(c_loc * n)
    idx, mag = ops.pair_mask_streams(
        seeds, jnp.ones((c_loc * n,), jnp.float32),
        nb=nb, k_mask=k_mask, m=m, p=p, q=q)
    return _client_mask_layout(idx.reshape(c_loc, n, nb, k_mask),
                               mag.reshape(c_loc, n, nb, k_mask),
                               signs_rows, nb, k_mask)


def fold_pair_key_matrix(mask_key: jax.Array, n: int):
    """In-trace [n, n] pair keys + signs for positional participants 0..n-1.

    The datacenter path has no host-side client ids (participants are mesh
    positions); the pair secret is a fold_in chain of the round key over the
    unordered pair — both endpoints derive the same key, as with dh_agree.
    """
    keys = [[jax.random.fold_in(jax.random.fold_in(mask_key, min(i, j)),
                                max(i, j))
             for j in range(n)] for i in range(n)]
    keys = jnp.stack([jnp.stack(row) for row in keys])
    signs = jnp.array(
        [[0.0 if i == j else (1.0 if i < j else -1.0) for j in range(n)]
         for i in range(n)], jnp.float32)
    return keys, signs


def fold_pair_keys_row(mask_key: jax.Array, self_id: jax.Array, n: int):
    """One participant's row of fold_in pair keys/signs, for traced self_id
    (the shard_map path, where self_id = lax.axis_index). Matches
    ``fold_pair_key_matrix(mask_key, n)[self_id]``."""
    keys, signs = [], []
    for peer in range(n):
        lo = jnp.minimum(self_id, peer)
        hi = jnp.maximum(self_id, peer)
        keys.append(jax.random.fold_in(jax.random.fold_in(mask_key, lo), hi))
        signs.append(jnp.where(self_id < peer, 1.0, -1.0)
                     * (self_id != peer).astype(jnp.float32))
    return jnp.stack(keys), jnp.stack(signs)


def pairwise_mask_rows(
    pair_keys_row: jax.Array,   # [n_peers] typed keys (this client's row)
    signs_row: jax.Array,       # f32[n_peers], 0 for the self slot
    nb: int,
    k_mask: int,
    m: int,
    *,
    p: float,
    q: float,
    leaf_id: int | jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One client's concatenated mask support/values over all peers.

    Per peer: ``k_mask`` pseudo-random positions per block in [0, m) and
    uniform magnitudes in [p, p+q), signed by the Bonawitz convention.
    For ``nb == 1`` this reproduces ``masks.pair_mask`` draw-for-draw.
    Returns (idx int32[nb, n_peers*k_mask], vals f32[nb, n_peers*k_mask]).
    """
    n_peers = pair_keys_row.shape[0]

    def one_peer(pk, sign):
        if leaf_id is not None:
            pk = jax.random.fold_in(pk, leaf_id)
        k_i, k_v = jax.random.split(pk)
        pidx = jax.random.randint(k_i, (nb, k_mask), 0, m, dtype=jnp.int32)
        pval = jax.random.uniform(k_v, (nb, k_mask), minval=p, maxval=p + q,
                                  dtype=jnp.float32)
        return pidx, sign * pval

    pidx, pval = jax.vmap(one_peer)(pair_keys_row, signs_row)  # [n_peers,nb,km]
    idx = jnp.moveaxis(pidx, 0, 1).reshape(nb, n_peers * k_mask)
    vals = jnp.moveaxis(pval, 0, 1).reshape(nb, n_peers * k_mask)
    return idx, vals


# ------------------------------------------------------------- batched encode
def encode_client_blocks(
    acc: jax.Array,             # f32[nb, m] one client's accumulator
    k: int,
    *,
    selector: str = "exact",
    sample_frac: float = 0.01,
    pair_keys_row: jax.Array | None = None,   # [n_peers] typed keys
    pair_signs_row: jax.Array | None = None,  # f32[n_peers], 0 = self slot
    mask_idx: jax.Array | None = None,   # precomputed int32[nb, n_peers*k_mask]
    mask_vals: jax.Array | None = None,  # precomputed f32 (counter-based path)
    k_mask: int = 0,
    mask_p: float = -1.0,
    mask_q: float = 2.0,
    leaf_id: int | jax.Array | None = None,
    weight: jax.Array | float = 1.0,
    dp_support: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One client's full encode: pairwise masks + unified stream, block view.

    Mask support arrives either precomputed (``mask_idx``/``mask_vals`` from
    the fused counter-based pass, plus ``pair_signs_row`` for the self gate)
    or is generated here from legacy jax.random pair keys. Returns
    (global_idx int32[nb, k_total], vals, new_acc). ``global_idx`` is
    ``row*m + col`` — flat into the padded block space (equals the flat leaf
    index when nb == 1). vmap-polymorphic: both the batched entry below and the
    shard_map datacenter path (traced self_id) call this. ``dp_support``
    switches the data slots onto the round's public common support
    (``unified_stream_rows``; core/dp.py).
    """
    nb, m = acc.shape
    if mask_idx is not None and k_mask > 0:
        m_idx, m_vals = mask_idx, mask_vals
    elif pair_keys_row is not None and k_mask > 0:
        m_idx, m_vals = pairwise_mask_rows(
            pair_keys_row, pair_signs_row, nb, k_mask, m,
            p=mask_p, q=mask_q, leaf_id=leaf_id)
    else:
        m_idx = m_vals = None
    if m_idx is not None and dp_support is None:
        # Inactive (self) slots carry zero mask value; point their support
        # at the block's top-1 position so first-occurrence gating zeroes
        # the slot entirely — a random support index there would transmit
        # the raw gradient unmasked.
        top1 = jnp.argmax(jnp.abs(acc), -1).astype(jnp.int32)[:, None]
        col_active = jnp.repeat(pair_signs_row != 0.0, k_mask)[None, :]
        m_idx = jnp.where(col_active, m_idx, top1)
    # Under DP (dp_support set) mask slots carry no gradient values at all,
    # so the self slot is silent at its raw counter-drawn index already — and
    # the top-1 override above would leak argmax(|acc|) through a transmitted
    # index, which the public-support release exists to prevent.
    idx, vals, new_acc = unified_stream_rows(
        acc, k, m_idx, m_vals, selector=selector,
        sample_frac=sample_frac, weight=weight, dp_support=dp_support)
    rows = jnp.arange(nb, dtype=jnp.int32)[:, None]
    return (rows * m + idx).astype(jnp.int32), vals, new_acc


def encode_batch_blocks(
    acc: jax.Array,             # f32[C, nb, m] stacked accumulators
    k: int,
    *,
    selector: str = "exact",
    sample_frac: float = 0.01,
    pair_keys: jax.Array | None = None,   # [C, C] typed keys (legacy path)
    pair_signs: jax.Array | None = None,  # f32[C, C]
    pair_seeds: jax.Array | None = None,  # uint32[C, C] counter seeds
    k_mask: int = 0,
    mask_p: float = -1.0,
    mask_q: float = 2.0,
    leaf_id: int | jax.Array | None = None,
    weights: jax.Array | None = None,     # f32[C] client-side gradient weights
    dp_support: jax.Array | None = None,  # int32[nb, k] public common support
) -> tuple[StreamBatch, jax.Array]:
    """Batched client encode: all clients of a round in one vmapped program.

    With ``pair_seeds`` (the repro/secagg protocol path) every pair mask of
    the round is generated counter-based in one fused pass *before* the vmap
    (``mask_streams_all_pairs``); ``pair_keys`` selects the legacy jax.random
    per-client generation instead. Returns (StreamBatch with *global* indices
    row*m + col, new_acc [C, nb, m]). The caller owns the block view
    (``to_blocks``/``from_blocks`` or the sharding-aligned transform of
    core/blocked.py) and the error-feedback accumulate ``acc = residual +
    update``. ``dp_support`` (one support, shared by every client — that is
    the point) routes the encode through the DP release shape (core/dp.py).
    """
    C, nb, m = acc.shape
    if weights is None:
        weights = jnp.ones((C,), jnp.float32)
    use_seeds = pair_seeds is not None and k_mask > 0 and C >= 2
    use_keys = (not use_seeds and pair_keys is not None and k_mask > 0
                and C >= 2)

    if use_seeds:
        m_idx, m_vals = mask_streams_all_pairs(
            pair_seeds, pair_signs, nb, k_mask, m,
            p=mask_p, q=mask_q, leaf_id=leaf_id)

        def one_seeded(acc_c, m_idx_c, m_vals_c, signs_row, w_c):
            return encode_client_blocks(
                acc_c, k, selector=selector, sample_frac=sample_frac,
                mask_idx=m_idx_c, mask_vals=m_vals_c,
                pair_signs_row=signs_row, k_mask=k_mask,
                mask_p=mask_p, mask_q=mask_q, weight=w_c,
                dp_support=dp_support)

        gidx, vals, new_acc = jax.vmap(one_seeded)(
            acc, m_idx, m_vals, pair_signs, weights)
        return StreamBatch(indices=gidx, values=vals), new_acc

    def one_client(acc_c, keys_row, signs_row, w_c):
        return encode_client_blocks(
            acc_c, k, selector=selector, sample_frac=sample_frac,
            pair_keys_row=keys_row, pair_signs_row=signs_row,
            k_mask=k_mask if use_keys else 0, mask_p=mask_p, mask_q=mask_q,
            leaf_id=leaf_id, weight=w_c, dp_support=dp_support)

    if use_keys:
        gidx, vals, new_acc = jax.vmap(one_client)(
            acc, pair_keys, pair_signs, weights)
    else:
        gidx, vals, new_acc = jax.vmap(
            lambda a, w: one_client(a, None, None, w))(acc, weights)
    return StreamBatch(indices=gidx, values=vals), new_acc


# ----------------------------------------------------- wire-format codec stage
def codec_wire_stage(gidx, vals, new_acc, weights, m: int, codec: str):
    """The client-side StreamCodec stage (DESIGN.md §12), mask-free rounds only.

    Quantizes the batched stream values row-wise, absorbs the quantization
    error into the error-feedback accumulator (transmitted positions were just
    zeroed by ``unified_stream_rows``; they now carry ``(sent - wire)/weight``
    so the error re-enters next round's accumulator and accuracy doesn't
    drift), and sorts each block row by column for the delta-packed index
    wire. Returns ``(cols int32[C, nb, k] sorted, q int32[C, nb, k],
    scales f32[C, nb], new_acc)``.
    """
    from repro.core import codecs

    C = gidx.shape[0]
    nb = gidx.shape[1]
    w = (jnp.asarray(weights, jnp.float32) if weights is not None
         else jnp.ones((C,), jnp.float32))
    q, scales = codecs.quantize_rows(vals, codec)
    vq = codecs.dequantize_rows(q, scales)
    cols = gidx % m
    err = (vals - vq) / jnp.where(w == 0.0, 1.0, w)[:, None, None]
    rows = jnp.arange(nb)[:, None]
    new_acc = jax.vmap(lambda a, c2, e: a.at[rows, c2].add(e))(
        new_acc, cols, err)
    order = jnp.argsort(cols, axis=-1)
    cols_s = jnp.take_along_axis(cols, order, -1)
    q_s = jnp.take_along_axis(q, order, -1)
    return cols_s, q_s, scales, new_acc


def codec_wire_roundtrip(cols_s, q_s, scales, m: int, codec: str):
    """Physically pack -> unpack -> dequantize one batched stream, so every
    round exercises the exact uint32 word wire (kernels/pack.py). The round
    trip is lossless: same sorted cols back, values on the quantization
    lattice. Returns ``(cols int32[C, nb, k], vq f32[C, nb, k])``."""
    from repro.core import codecs

    iw, vw = codecs.pack_stream_rows(cols_s, q_s, m=m, codec=codec)
    cols2, q2 = codecs.unpack_stream_rows(iw, vw, k=q_s.shape[-1], m=m,
                                          codec=codec)
    return cols2, codecs.dequantize_rows(q2, scales)




@functools.partial(
    jax.jit,
    static_argnames=("k", "nb", "m", "size", "selector", "sample_frac",
                     "k_mask", "mask_p", "mask_q", "codec", "dp_sigma"))
def encode_leaf_batch(
    updates: jax.Array,        # [C, *leaf_shape] stacked client updates
    residuals: jax.Array,      # [C, *leaf_shape] stacked error feedback
    *,
    k: int,
    nb: int,
    m: int,
    size: int,
    selector: str = "exact",
    sample_frac: float = 0.01,
    pair_keys: jax.Array | None = None,
    pair_signs: jax.Array | None = None,
    pair_seeds: jax.Array | None = None,
    k_mask: int = 0,
    mask_p: float = -1.0,
    mask_q: float = 2.0,
    leaf_id: int | jax.Array = 0,
    weights: jax.Array | None = None,
    codec: str = "f32",
    dp_sigma: float = 0.0,
    dp_seeds: jax.Array | None = None,
    dp_support_seed: jax.Array | int = 0,
) -> tuple[StreamBatch, jax.Array]:
    """Jitted leaf-level encode: accumulate -> block view -> batched encode.

    The single entry point the reference server (core/fedavg.py) uses per
    leaf and round. One compiled program per (leaf shape, ``k``, ``k_mask``)
    covers every client — this replaced the seed's serial per-client
    ``encode_update`` loop. ``leaf_id`` is traced (it only feeds ``fold_in``),
    so same-shaped leaves share one executable; the time-varying ``k``
    schedule is the only remaining re-specialization source (quantized by
    ``THGSConfig.k_levels`` — see DESIGN.md §9 for the sim engine's
    compile-once contract).

    Parameters
    ----------
    updates : f32-castable[C, *leaf_shape]
        Stacked client updates (local model deltas) for one leaf.
    residuals : [C, *leaf_shape]
        Stacked per-client error-feedback accumulators; the encode operates
        on ``residuals + updates``.
    k : int
        Top-k slots per block (static; one value serves all clients).
    nb, m, size : int
        Block layout: ``nb`` blocks of length ``m`` covering the ``size``
        -element leaf (``nb == 1, m == size`` is the flat single-host
        protocol; see ``block_layout``).
    selector : {'exact', 'sampled', 'local'}
        Top-k selector (THGSConfig.selector).
    sample_frac : float
        Subsample fraction for ``selector='sampled'``.
    pair_keys, pair_signs : [C, C] typed keys / f32[C, C], optional
        Legacy jax.random pairwise-mask key matrix and Bonawitz signs from
        ``pair_key_matrix``; ``None`` encodes without secure aggregation.
    pair_seeds : uint32[C, C], optional
        Counter-based pair seeds from ``pair_seed_matrix`` (the repro/secagg
        protocol path); takes precedence over ``pair_keys`` and routes mask
        generation through the fused kernel/oracle data plane.
    k_mask : int
        Mask-support slots per pair per block (Eq. 4); 0 disables masking.
    mask_p, mask_q : float
        Uniform mask support ``[p, p + q)`` (paper §3.2).
    leaf_id : int or traced int
        Folded into every pair key so leaves draw independent masks.
    weights : f32[C], optional
        Client-side aggregation weights applied to the gradient values
        *before* masking (module docstring); None means uniform.
    codec : {'f32', 'int8', 'int4', '1bit'}
        Stream value wire codec (core/codecs.py, DESIGN.md §12). Non-f32
        codecs quantize the values (error absorbed into the returned
        residuals), sort + delta-pack the indices, and run the packed wire
        round trip in-trace; they require ``k_mask == 0`` — pair masks cancel
        only on the f32 grid.
    dp_sigma : float (static)
        Per-client DP noise stddev (``DPConfig.sigma_client``); > 0 switches
        the encode into its DP release shape (core/dp.py, DESIGN.md §15):
        the k data slots release the round's PUBLIC common support instead
        of the data-dependent top-k, mask slots carry masks only, and
        grid-exact Gaussian noise is added to every released slot under the
        pair masks. 0 statically skips the stage, so DP-off rounds are
        bit-identical to pre-DP rounds. Requires the f32 codec, ``dp_seeds``
        and ``dp_support_seed``.
    dp_seeds : uint32[C], optional
        Per-(round, client) noise-stream seeds (``DPConfig.client_seeds``),
        folded with ``leaf_id`` in-trace like the pair seeds.
    dp_support_seed : uint32 scalar
        The round's common-support seed (``DPConfig.support_seed``) — a pure
        function of (dp seed, round), shared by the cohort; folded with
        ``leaf_id`` in-trace. Only read when ``dp_sigma > 0``.

    Returns
    -------
    streams : StreamBatch
        ``indices`` int32[C, nb, k_total] global (``row*m + col``) indices and
        ``values`` f32[C, nb, k_total], where ``k_total = k + C*k_mask``
        (the gated self-pair slot is never counted on the wire — Eq. 6
        accounting uses ``k + (C-1)*k_mask``).
    new_residuals : [C, *leaf_shape]
        Updated error feedback: transmitted positions zeroed, same dtype as
        ``residuals``.
    """
    leaf_shape = updates.shape[1:]
    reject_codec_with_masks(codec, k_mask)
    dp_on = dp_sigma > 0.0
    dp_support = None
    if dp_on:
        from repro.core import dp as dp_mod

        dp_mod.reject_codec_with_noise(codec, dp_sigma)
        if dp_seeds is None:
            raise ValueError("dp_sigma > 0 requires dp_seeds")
        dp_support = dp_mod.common_support(
            dp_support_seed, nb, min(int(k), m), m, leaf_id)
    acc = jax.vmap(lambda u, r: to_blocks(
        r.astype(jnp.float32) + u.astype(jnp.float32), nb, m))(
            updates, residuals)
    streams, new_acc = encode_batch_blocks(
        acc, k, selector=selector, sample_frac=sample_frac,
        pair_keys=pair_keys, pair_signs=pair_signs, pair_seeds=pair_seeds,
        k_mask=k_mask, mask_p=mask_p, mask_q=mask_q, leaf_id=leaf_id,
        weights=weights, dp_support=dp_support)
    if dp_on:
        streams = StreamBatch(
            indices=streams.indices,
            values=dp_mod.add_stream_noise(
                streams.values, dp_seeds, sigma=dp_sigma, leaf_id=leaf_id,
                k_data=min(int(k), m)))
    if codec != "f32":
        cols, q, scales, new_acc = codec_wire_stage(
            streams.indices, streams.values, new_acc, weights, m, codec)
        cols, vq = codec_wire_roundtrip(cols, q, scales, m, codec)
        rows_b = jnp.arange(nb, dtype=jnp.int32)[None, :, None]
        streams = StreamBatch(indices=(rows_b * m + cols).astype(jnp.int32),
                              values=vq)
    new_res = jax.vmap(lambda b: from_blocks(b, size, leaf_shape))(new_acc)
    return streams, new_res.astype(residuals.dtype)


# ------------------------------------------------------------- server decode
def _scatter_flat(flat_idx: jax.Array, flat_vals: jax.Array,
                  padded: int, use_pallas: bool) -> jax.Array:
    if use_pallas:
        from repro.kernels import ops

        return ops.stream_scatter_add(flat_idx, flat_vals, size=padded)
    return jnp.zeros((padded,), jnp.float32).at[flat_idx].add(flat_vals)


def _flatten_round_stream(
    streams: StreamBatch,
    alive: jax.Array | None,
    weights: jax.Array | None,
    extra: StreamBatch | None,
) -> tuple[jax.Array, jax.Array]:
    """The round's single flat (idx, vals) stream: per-client gating applied,
    recovery streams appended. Shared by the flat and tree decodes so both
    topologies fold the *identical* slot sequence (DESIGN.md §13)."""
    C = streams.indices.shape[0]
    gate = jnp.ones((C,), jnp.float32)
    if weights is not None:
        gate = gate * jnp.asarray(weights, jnp.float32)
    if alive is not None:
        gate = gate * jnp.asarray(alive, jnp.float32)
    vals = streams.values * gate[:, None, None]
    flat_idx = streams.indices.reshape(-1)
    flat_vals = vals.reshape(-1)
    if extra is not None:
        flat_idx = jnp.concatenate([flat_idx, extra.indices.reshape(-1)])
        flat_vals = jnp.concatenate(
            [flat_vals, extra.values.reshape(-1).astype(jnp.float32)])
    return flat_idx, flat_vals


def decode_sum_blocks(
    streams: StreamBatch,      # [C, nb, k_total] global indices/values
    nb: int,
    m: int,
    *,
    alive: jax.Array | None = None,      # bool/f32[C] survivor gate
    weights: jax.Array | None = None,    # f32[C] server-side weights (uniform
                                         # protocols only — see module doc)
    extra: StreamBatch | None = None,    # reconstruction streams, weight 1
    use_pallas: bool | None = None,
) -> jax.Array:
    """Scatter-add every client's stream into the dense [nb*m] buffer — one
    fused pass (Pallas on TPU, XLA scatter elsewhere). Returns f32[nb*m]."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    flat_idx, flat_vals = _flatten_round_stream(streams, alive, weights,
                                                extra)
    return _scatter_flat(flat_idx, flat_vals, nb * m, use_pallas)


# ------------------------------------------- hierarchical (tree) decode (§13)
def tree_splits(padded: int, n_groups: int) -> tuple[int, ...]:
    """Near-even contiguous index-range boundaries for ``n_groups``
    sub-aggregators over a ``padded``-element dense buffer.

    Returns ``G + 1`` monotone boundaries ``(0, ..., padded)``; group ``g``
    owns ``[splits[g], splits[g+1])``. ``n_groups`` is clamped to
    ``[1, padded]`` (a group must own at least one position). Any monotone
    boundary tuple is a valid partition for :func:`decode_sum_tree` — the
    property suite exercises arbitrary uneven ones.
    """
    G = max(1, min(int(n_groups), int(padded)))
    base, rem = divmod(int(padded), G)
    bounds = [0]
    for g in range(G):
        bounds.append(bounds[-1] + base + (1 if g < rem else 0))
    return tuple(bounds)


def scatter_steps(n_clients: int, k: int, k_mask: int, nb: int, m: int, *,
                  recovery: bool = False, splits: Sequence[int] = ()) -> int:
    """Grid steps of the Pallas decode for one leaf's round stream: every
    client's ``min(k, m) + C*k_mask`` slots per block (``encode_leaf_batch``)
    plus, with ``recovery``, the ``C*C*k_mask`` Bonawitz streams per block.
    One kernel call for the flat decode; one per non-empty range of
    ``splits`` for the tree decode, each over the whole stream."""
    from repro.kernels.stream_decode import grid_steps

    C = n_clients
    n = C * nb * (min(int(k), m) + C * k_mask) + (
        C * C * nb * k_mask if recovery else 0)
    if not splits:
        return grid_steps(n, nb * m)
    return sum(grid_steps(n, hi - lo)
               for lo, hi in zip(splits[:-1], splits[1:]) if hi > lo)


def _scatter_range(flat_idx: jax.Array, flat_vals: jax.Array,
                   lo: int, hi: int, use_pallas: bool) -> jax.Array:
    """One sub-aggregator's partial: scatter the slots landing in
    ``[lo, hi)`` of the padded buffer, in the round stream's slot order.

    The Pallas kernel is handed the whole stream shifted by ``lo`` and drops
    the slots outside ``[0, width)`` itself: its sort then orders the slots
    as the flat decode's does, and its chunk windows (global to the sorted
    stream) group each position's slots into the same contractions, so the
    partial is bit-exact with the flat scatter (kernels/stream_decode.py).

    On the XLA scatter, out-of-range slots are redirected to a dump slot at
    position ``width`` (buffer ``width + 1``, sliced off on return) with
    value 0.0 — NOT zeroed in place: an in-range position must never
    receive a redirected ``+0.0`` (``-0.0 + 0.0 == +0.0`` would flip the
    sign bit of a ``-0.0`` partial and break bit-exactness with the flat
    scatter).
    """
    width = hi - lo
    if use_pallas:
        return _scatter_flat(flat_idx - lo, flat_vals, width, use_pallas)
    in_range = (flat_idx >= lo) & (flat_idx < hi)
    local = jnp.where(in_range, flat_idx - lo, width)
    vals = jnp.where(in_range, flat_vals, 0.0)
    return _scatter_flat(local, vals, width + 1, use_pallas)[:width]


def decode_sum_tree(
    streams: StreamBatch,      # [C, nb, k_total] global indices/values
    nb: int,
    m: int,
    *,
    splits: Sequence[int],               # G + 1 boundaries (tree_splits)
    alive: jax.Array | None = None,      # bool/f32[C] survivor gate
    weights: jax.Array | None = None,    # f32[C] server-side weights
    extra: StreamBatch | None = None,    # reconstruction streams, weight 1
    use_pallas: bool | None = None,
) -> jax.Array:
    """Hierarchical decode: G sub-aggregators each scatter-add the round
    stream's slots landing in their contiguous index range of the dense
    buffer; the inter-group combine is pure concatenation. Returns f32[nb*m].

    Because each position of the buffer is owned by exactly one group and
    every group folds its positions' contributions in the same slot order as
    the flat decode, the result is **bit-exact** with
    :func:`decode_sum_blocks` for *any* partition — the combine performs zero
    floating-point additions (DESIGN.md §13; client-group dense partials
    would re-associate f32 sums and drift). Mask cancellation needs no
    protocol change: both endpoints of every pair mask target the same
    positions, so their slots route to the same sub-aggregator and cancel
    inside its partial.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    splits = tuple(int(s) for s in splits)
    if len(splits) < 2 or splits[0] != 0 or splits[-1] != nb * m or \
            any(b < a for a, b in zip(splits, splits[1:])):
        raise ValueError(
            f"splits must be monotone boundaries (0, ..., {nb * m}), "
            f"got {splits}")
    flat_idx, flat_vals = _flatten_round_stream(streams, alive, weights,
                                                extra)
    parts = [_scatter_range(flat_idx, flat_vals, lo, hi, use_pallas)
             for lo, hi in zip(splits[:-1], splits[1:]) if hi > lo]
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def dropout_cancel_streams(
    pair_keys: jax.Array,    # [C, C] typed keys (as used at encode time)
    pair_signs: jax.Array,   # f32[C, C]
    alive: jax.Array,        # bool[C]
    nb: int,
    k_mask: int,
    m: int,
    *,
    p: float,
    q: float,
    leaf_id: int | jax.Array | None = None,
) -> StreamBatch:
    """Bonawitz dropout recovery: regenerate every survivor→dropped pair mask
    and emit its negation, so the survivor sum's unpaired masks cancel.

    In the real protocol the server learns the pair secrets of dropped clients
    via Shamir shares; here it regenerates them from the same pair keys the
    encode used. Pairs are gated by ``alive[s] & ~alive[d]`` — survivor/survivor
    masks already cancel pairwise, dropped/dropped streams never arrived.
    """
    C = pair_keys.shape[0]
    alive_f = jnp.asarray(alive, jnp.float32)

    def one_pair(pk, sign, gate):
        idx, vals = pairwise_mask_rows(
            pk[None], sign[None], nb, k_mask, m, p=p, q=q, leaf_id=leaf_id)
        return idx, -gate * vals

    gates = alive_f[:, None] * (1.0 - alive_f[None, :])   # [C, C] s alive, d not
    flat_keys = pair_keys.reshape(C * C)
    flat_signs = pair_signs.reshape(C * C)
    flat_gates = gates.reshape(C * C)
    idx, vals = jax.vmap(one_pair)(flat_keys, flat_signs, flat_gates)
    idx = idx.reshape(C * C, nb, k_mask)
    # decode consumes GLOBAL indices (row*m + col); nb == 1 leaves this a
    # no-op, the blocked layout needs the row offset
    idx = jnp.arange(nb, dtype=jnp.int32)[None, :, None] * m + idx
    return StreamBatch(indices=idx,
                       values=vals.reshape(C * C, nb, k_mask))


def dropout_cancel_streams_seeded(
    pair_seeds: jax.Array,   # uint32[C, C] counter seeds (reconstructed or
                             # original — only survivor→dropped entries used)
    pair_signs: jax.Array,   # f32[C, C]
    alive: jax.Array,        # bool[C]
    nb: int,
    k_mask: int,
    m: int,
    *,
    p: float,
    q: float,
    leaf_id: int | jax.Array | None = None,
) -> StreamBatch:
    """Bonawitz dropout recovery on the counter-based data plane.

    Regenerates every survivor→dropped pair mask from the (Shamir-
    reconstructed) pair seeds in one fused pass and emits its negation; pairs
    outside the ``alive[s] & ~alive[d]`` gate contribute zero values, so a
    seed matrix filled only at the recovered entries is sufficient. Survivor/
    survivor masks already cancel pairwise, dropped/dropped streams never
    arrived. Bit-identical to the masks the encode applied — the property
    tests/test_secagg_protocol.py pins.
    """
    from repro.kernels import ops

    C = pair_seeds.shape[0]
    alive_f = jnp.asarray(alive, jnp.float32)
    seeds = _fold_seeds(pair_seeds, leaf_id).reshape(C * C)
    idx, vals = ops.pair_mask_streams(
        seeds, jnp.asarray(pair_signs, jnp.float32).reshape(C * C),
        nb=nb, k_mask=k_mask, m=m, p=p, q=q)
    gates = (alive_f[:, None] * (1.0 - alive_f[None, :])).reshape(C * C)
    vals = -gates[:, None, None] * vals
    idx = jnp.arange(nb, dtype=jnp.int32)[None, :, None] * m + idx
    return StreamBatch(indices=idx, values=vals)


@functools.partial(
    jax.jit,
    static_argnames=("nb", "m", "size", "k_mask", "mask_p", "mask_q",
                     "use_pallas"))
def decode_leaf_batch(
    streams: StreamBatch,
    *,
    nb: int,
    m: int,
    size: int,
    alive: jax.Array | None = None,
    weights: jax.Array | None = None,
    pair_keys: jax.Array | None = None,
    pair_signs: jax.Array | None = None,
    pair_seeds: jax.Array | None = None,
    k_mask: int = 0,
    mask_p: float = -1.0,
    mask_q: float = 2.0,
    leaf_id: int | jax.Array = 0,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Jitted server decode for one leaf: survivor-gated fused scatter-add,
    plus reconstructed-mask cancellation when ``alive`` marks dropouts.

    Parameters
    ----------
    streams : StreamBatch
        All clients' unified streams for the leaf, as produced by
        ``encode_leaf_batch`` (global indices, leading client axis).
    nb, m, size : int
        Block layout the streams were encoded under; the dense buffer is
        ``nb * m`` padded elements, truncated to ``size`` on return.
    alive : bool[C], optional
        Survivor gate: False rows' streams are excluded (their upload never
        arrived). When given together with ``pair_seeds`` (or legacy
        ``pair_keys``) and ``k_mask``, the survivors' unpaired masks toward
        the dropped clients are regenerated and cancelled
        (``dropout_cancel_streams_seeded`` / ``dropout_cancel_streams`` —
        Bonawitz recovery). On the protocol path the seeds are the Shamir-
        reconstructed ones (repro/secagg), not the encode-time originals.
    weights : f32[C], optional
        Server-side per-stream scaling. Only correct for protocols whose
        masks cancel under it (uniform weighting); weighted FL applies
        weights client-side at encode time instead (module docstring).
    pair_keys, pair_signs, k_mask, mask_p, mask_q, leaf_id
        The mask parameters the encode used; needed only for dropout
        recovery.
    use_pallas : bool, optional
        Force the fused Pallas scatter kernel (TPU default) or the XLA
        scatter fallback; ``None`` picks by backend.

    Returns
    -------
    f32[size]
        The dense aggregate of the surviving clients' weighted sparse
        updates, masks cancelled. The caller normalizes by the survivors'
        total weight (core/fedavg.py).
    """
    extra = None
    if alive is not None and pair_seeds is not None and k_mask > 0:
        extra = dropout_cancel_streams_seeded(
            pair_seeds, pair_signs, alive, nb, k_mask, m,
            p=mask_p, q=mask_q, leaf_id=leaf_id)
    elif alive is not None and pair_keys is not None and k_mask > 0:
        extra = dropout_cancel_streams(
            pair_keys, pair_signs, alive, nb, k_mask, m,
            p=mask_p, q=mask_q, leaf_id=leaf_id)
    dense = decode_sum_blocks(
        streams, nb, m, alive=alive, weights=weights, extra=extra,
        use_pallas=use_pallas)
    return dense[:size]


@functools.partial(
    jax.jit,
    static_argnames=("nb", "m", "size", "splits", "k_mask", "mask_p",
                     "mask_q", "use_pallas"))
def decode_leaf_tree(
    streams: StreamBatch,
    *,
    nb: int,
    m: int,
    size: int,
    splits: tuple,
    alive: jax.Array | None = None,
    weights: jax.Array | None = None,
    pair_signs: jax.Array | None = None,
    pair_seeds: jax.Array | None = None,
    k_mask: int = 0,
    mask_p: float = -1.0,
    mask_q: float = 2.0,
    leaf_id: int | jax.Array = 0,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Hierarchical twin of :func:`decode_leaf_batch`: identical arguments
    plus the static ``splits`` boundary tuple (see :func:`tree_splits`), and
    identical — bit-exact — output. Dropout recovery streams join the round
    stream before range routing, so each sub-aggregator cancels the
    reconstruction masks landing in its own index range (DESIGN.md §13)."""
    extra = None
    if alive is not None and pair_seeds is not None and k_mask > 0:
        extra = dropout_cancel_streams_seeded(
            pair_seeds, pair_signs, alive, nb, k_mask, m,
            p=mask_p, q=mask_q, leaf_id=leaf_id)
    dense = decode_sum_tree(
        streams, nb, m, splits=splits, alive=alive, weights=weights,
        extra=extra, use_pallas=use_pallas)
    return dense[:size]


# ----------------------------------------------------- the stream exchange
def all_gather_round(tree, axis_name: str, *, tiled: bool = False,
                     replicate: bool = False):
    """all_gather every array of one round's wire payload over the
    federation/clients axis — the ONE collective of the sparse exchange
    (DESIGN.md §11/§12). Every stream consumer (the sharded round below, both
    launch/train.py step builders) routes its gather through here, so a new
    wire payload (e.g. packed codec words) lands in one place.

    ``replicate`` first pins each leaf replicated *within* the participant
    ("gather to leader, then exchange"): XLA's partial-manual partitioner
    cannot form cross-participant peer groups for tensors still sharded over
    the auto axes (hard CHECK) — the launcher's FL mesh needs this, the
    full-manual clients mesh does not.
    """
    def g(x):
        if replicate:
            x = jax.lax.with_sharding_constraint(
                x, jax.sharding.PartitionSpec())
        return jax.lax.all_gather(x, axis_name, axis=0, tiled=tiled)

    return jax.tree_util.tree_map(g, tree)


def gather_streams(stream, axis_name: str, *, tiled: bool = False,
                   replicate: bool = False) -> StreamBatch:
    """Gather one participant's stream into the round's stacked
    ``StreamBatch`` (accepts anything with ``.indices``/``.values``)."""
    idx, vals = all_gather_round((stream.indices, stream.values), axis_name,
                                 tiled=tiled, replicate=replicate)
    return StreamBatch(indices=idx, values=vals)


# ----------------------------------------- client-parallel (sharded) round
def shard_map_clients(f, mesh, in_specs, out_specs):
    """Full-manual shard_map over the 1-D ``clients`` mesh. The
    partial-manual variant (manual over one axis of a larger mesh) lives in
    launch/train.py."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def shard_client_tree(tree, mesh):
    """Place every leaf of a client-stacked pytree (leading axis = clients)
    with its leading axis partitioned over the ``clients`` mesh — so the
    shard_map programs consume it without a gather-then-scatter reshard."""
    from jax.sharding import NamedSharding, PartitionSpec

    def put(x):
        spec = PartitionSpec(CLIENT_AXIS, *((None,) * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(put, tree)


def can_shard_clients(mesh, n_clients: int) -> bool:
    """True iff ``mesh`` can host a client-parallel round for this cohort:
    a >1-device 1-D ``clients`` mesh whose size divides the cohort evenly
    (shard_map needs equal shards). Callers fall back to the vmap path
    otherwise."""
    if mesh is None:
        return False
    if tuple(mesh.axis_names) != (CLIENT_AXIS,):
        return False
    n_dev = mesh.devices.size
    return n_dev > 1 and n_clients % n_dev == 0


@functools.lru_cache(maxsize=None)
def _sharded_leaf_program(mesh, k: int, nb: int, m: int, size: int,
                          selector: str, sample_frac: float, k_mask: int,
                          mask_p: float, mask_q: float, with_dropout: bool,
                          use_pallas, codec: str = "f32",
                          splits: tuple = (), dp_sigma: float = 0.0):
    """Build + cache the jitted shard_map program for one leaf signature.

    The cache key is the static signature (mesh + block layout + schedule
    ``k`` + mask config); jit itself re-specializes on shapes/dtypes. One
    program per (leaf shape, k, k_mask) — the same re-specialization budget
    as the serial ``encode_leaf_batch``/``decode_leaf_batch`` pair.
    """
    P = jax.sharding.PartitionSpec
    with_masks = k_mask > 0

    def body(updates_l, residuals_l, weights_l, pair_seeds, pair_signs,
             recovery_seeds, alive, dp_seeds, dp_support_seed, leaf_id):
        c_loc = updates_l.shape[0]
        leaf_shape = updates_l.shape[1:]
        acc = jax.vmap(lambda u, r: to_blocks(
            r.astype(jnp.float32) + u.astype(jnp.float32), nb, m))(
                updates_l, residuals_l)
        i0 = jax.lax.axis_index(CLIENT_AXIS) * c_loc
        dp_support = None
        if dp_sigma > 0.0:
            from repro.core import dp as dp_mod

            # every device derives the IDENTICAL public support from the
            # replicated (round, leaf) seed — common across the whole cohort,
            # bit-identical with the serial encode by construction
            dp_support = dp_mod.common_support(
                dp_support_seed, nb, min(int(k), m), m, leaf_id)
        if with_masks:
            seeds_rows = jax.lax.dynamic_slice_in_dim(
                pair_seeds, i0, c_loc, 0)
            signs_rows = jax.lax.dynamic_slice_in_dim(
                jnp.asarray(pair_signs, jnp.float32), i0, c_loc, 0)
            m_idx, m_vals = mask_streams_rows(
                seeds_rows, signs_rows, nb, k_mask, m,
                p=mask_p, q=mask_q, leaf_id=leaf_id)

            def one(acc_c, mi, mv, srow, w_c):
                return encode_client_blocks(
                    acc_c, k, selector=selector, sample_frac=sample_frac,
                    mask_idx=mi, mask_vals=mv, pair_signs_row=srow,
                    k_mask=k_mask, mask_p=mask_p, mask_q=mask_q, weight=w_c,
                    dp_support=dp_support)

            gidx, vals, new_acc = jax.vmap(one)(
                acc, m_idx, m_vals, signs_rows, weights_l)
        else:
            def one_plain(acc_c, w_c):
                return encode_client_blocks(
                    acc_c, k, selector=selector, sample_frac=sample_frac,
                    weight=w_c, dp_support=dp_support)

            gidx, vals, new_acc = jax.vmap(one_plain)(acc, weights_l)
        if dp_sigma > 0.0:
            # each device noises its OWN clients' rows from the same seed
            # vector the serial round folds — bit-identical by construction
            dp_rows = jax.lax.dynamic_slice_in_dim(dp_seeds, i0, c_loc, 0)
            vals = dp_mod.add_stream_noise(
                vals, dp_rows, sigma=dp_sigma, leaf_id=leaf_id,
                k_data=min(int(k), m))
        # the server reduction: ONE collective over the clients axis. An
        # all_gather of the sparse streams (then the identical full fused
        # scatter-add on every device) rather than a psum of per-device dense
        # partials — the gather moves C*k_total stream slots instead of the
        # nb*m dense buffer, and, because every device then runs the very same
        # scatter over the very same flat stream, the sharded round is
        # bit-exact with the serial decode (a psum's tree-order partial sums
        # are not). With a quantized codec the gathered payload is the packed
        # wire itself — delta-packed index words + value words + row scales —
        # and every device unpacks/dequantizes the identical words, so the
        # codec round stays bit-exact with the serial codec round too (the
        # per-row quantize is shard-local and identical on both paths).
        if codec != "f32":
            from repro.core import codecs

            cols, q, scales, new_acc = codec_wire_stage(
                gidx, vals, new_acc, weights_l, m, codec)
            iw, vw = codecs.pack_stream_rows(cols, q, m=m, codec=codec)
            g_iw, g_vw, g_sc = all_gather_round(
                (iw, vw, scales), CLIENT_AXIS, tiled=True)
            cols_g, q_g = codecs.unpack_stream_rows(
                g_iw, g_vw, k=q.shape[-1], m=m, codec=codec)
            rows_b = jnp.arange(nb, dtype=jnp.int32)[None, :, None]
            g_idx = (rows_b * m + cols_g).astype(jnp.int32)
            g_val = codecs.dequantize_rows(q_g, g_sc)
        else:
            g_idx, g_val = all_gather_round((gidx, vals), CLIENT_AXIS,
                                            tiled=True)
        extra = None
        if with_dropout and with_masks:
            extra = dropout_cancel_streams_seeded(
                recovery_seeds, pair_signs, alive, nb, k_mask, m,
                p=mask_p, q=mask_q, leaf_id=leaf_id)
        gathered = StreamBatch(indices=g_idx, values=g_val)
        if splits:
            # hierarchical decode over the gathered stream: replicated on
            # fake CPU devices (like the flat scatter above), range-sharded
            # on real hierarchies — bit-exact either way (§13)
            dense = decode_sum_tree(
                gathered, nb, m, splits=splits,
                alive=alive if with_dropout else None, extra=extra,
                use_pallas=use_pallas)
        else:
            dense = decode_sum_blocks(
                gathered, nb, m,
                alive=alive if with_dropout else None, extra=extra,
                use_pallas=use_pallas)  # with_dropout: survivor gate
        new_res = jax.vmap(lambda b: from_blocks(b, size, leaf_shape))(
            new_acc).astype(residuals_l.dtype)
        return dense[:size], new_res

    fn = shard_map_clients(
        body, mesh,
        in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS), P(CLIENT_AXIS),
                  P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(CLIENT_AXIS)))
    return jax.jit(fn)


def encode_decode_leaf_sharded(
    mesh,
    updates: jax.Array,        # [C, *leaf_shape] stacked client updates
    residuals: jax.Array,      # [C, *leaf_shape] stacked error feedback
    *,
    k: int,
    nb: int,
    m: int,
    size: int,
    selector: str = "exact",
    sample_frac: float = 0.01,
    pair_seeds: jax.Array | None = None,
    pair_signs: jax.Array | None = None,
    recovery_seeds: jax.Array | None = None,
    alive: jax.Array | None = None,
    k_mask: int = 0,
    mask_p: float = -1.0,
    mask_q: float = 2.0,
    leaf_id: int | jax.Array = 0,
    weights: jax.Array | None = None,
    use_pallas: bool | None = None,
    codec: str = "f32",
    topology: str = "flat",
    tree_groups: int = 0,
    dp_sigma: float = 0.0,
    dp_seeds: jax.Array | None = None,
    dp_support_seed: jax.Array | int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Client-parallel encode + decode for one leaf, fused in one shard_map.

    The device-sharded twin of the ``encode_leaf_batch`` -> ``decode_leaf_batch``
    pair: clients are partitioned over the 1-D ``clients`` mesh, each device
    runs the THGS encode and pair-mask PRNG for its shard, and the server
    reduction is a single all_gather of the sparse streams followed by the
    same fused scatter-add on every device (bit-exact with the serial path —
    see the in-body comment for why not a dense psum). Dropout recovery
    (``alive`` + ``recovery_seeds``) replicates the reconstruction streams,
    exactly as the serial decode does.

    Requires ``can_shard_clients(mesh, C)``; returns
    ``(dense f32[size] replicated, new_residuals [C, *leaf_shape]
    client-sharded)``. The caller normalizes by the survivors' total weight,
    as with the serial pair.
    """
    C = updates.shape[0]
    assert can_shard_clients(mesh, C), (
        f"mesh {mesh} cannot shard {C} clients; use encode_leaf_batch")
    with_masks = pair_seeds is not None and k_mask > 0 and C >= 2
    reject_codec_with_masks(codec, k_mask if with_masks else 0)
    if dp_sigma > 0.0:
        from repro.core import dp as dp_mod

        dp_mod.reject_codec_with_noise(codec, dp_sigma)
        if dp_seeds is None:
            raise ValueError("dp_sigma > 0 requires dp_seeds")
    if dp_seeds is None:
        # placeholder operand keeps the program arity fixed; the dp_sigma
        # branch is baked statically so it is never read
        dp_seeds = jnp.zeros((C,), jnp.uint32)
    # dropouts gate the decode even without masks (serial parity: the serial
    # path passes `alive` to decode_leaf_batch whenever clients dropped);
    # recovery streams additionally need the masks
    with_dropout = alive is not None
    if weights is None:
        weights = jnp.ones((C,), jnp.float32)
    if not with_masks:
        k_mask = 0
        # placeholder operands keep the program arity fixed; the with_masks
        # branch is baked statically so they are never read
        pair_seeds = jnp.zeros((C, C), jnp.uint32)
        pair_signs = jnp.zeros((C, C), jnp.float32)
    if recovery_seeds is None:
        recovery_seeds = pair_seeds
    if alive is None:
        alive = jnp.ones((C,), bool)
    if topology not in ("flat", "tree"):
        raise ValueError(f"unknown topology {topology!r}")
    splits = ()
    if topology == "tree":
        splits = tree_splits(nb * m, tree_groups if tree_groups > 0
                             else max(2, int(round(C ** 0.5))))
    fn = _sharded_leaf_program(
        mesh, int(k), int(nb), int(m), int(size), selector,
        float(sample_frac), int(k_mask), float(mask_p), float(mask_q),
        bool(with_dropout), use_pallas, str(codec), splits, float(dp_sigma))
    return fn(updates, residuals, jnp.asarray(weights, jnp.float32),
              pair_seeds, pair_signs, recovery_seeds, alive,
              jnp.asarray(dp_seeds, jnp.uint32),
              jnp.asarray(dp_support_seed, jnp.uint32),
              jnp.asarray(leaf_id))
