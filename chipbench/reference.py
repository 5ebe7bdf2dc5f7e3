"""Plain reference of the federated rounds that decide ``correct``.

It follows the rounds a run compared from the same seed and the same inputs
(the cohort, the dropped clients and each client's batches), and imports
nothing of the program under test: the model is the configuration's plain
reference (``configs/<config>.py``), and the rest is written out here from
the paper's description and the repository's documented wire format:

* local SGD: ``local_steps`` plain SGD steps per client on mean cross
  entropy, at ``Precision.HIGHEST``;
* THGS encode (paper Alg. 1, Eq. 1): per leaf, ``k`` from Eq. 1's
  per-leaf rate snapped to ``k_levels`` geometric levels, on the
  error-feedback accumulator ``residual + delta``; of magnitudes that tie
  at the k-th place, the lowest indices are sent;
* sparse pair masks (Eq. 3-5): every client also transmits its gradient
  values on the support of its pair masks toward each cohort peer. The
  support comes from the DH-agreed pair seed (SHA-256 and modular
  exponentiation over GF(2^61 - 1)) through murmur-avalanched counters. The
  masks themselves cancel in the aggregate, survivor pairs among themselves
  and survivor-to-dropped pairs through Bonawitz recovery, so the reference
  sums the unmasked values;
* server: the survivors' transmitted values summed and divided by the
  number of survivors, added to the params (server lr). Transmitted
  positions leave the survivors' residuals; a dropped client keeps its whole
  accumulator.

``run`` follows the rounds free-running from the seed's weights.
``forced`` is the teacher-forced mode: it repeats each compared round in
float32 from the state that the other side (the program, or the control or
a fault put in its place) had at that round's start. Its local SGD starts
from the other side's params, and its aggregation (Eq. 1 top-k, mask
support, the survivors' sum, the server update, the residual write-back)
runs on the other side's own deltas and residuals. A sound aggregation then
agrees to rounding, whatever local SGD's rounding grew to over its steps.

``dtype='bfloat16'`` computes the same rounds in bfloat16 throughout: the
control, which the comparison must refuse. ``fault`` plants one of the
faults that the comparison must catch: ``half_batch`` (the loss of each
local step over the first half of its batch), ``lost_upload`` (one
survivor's values left out of the aggregate), ``no_recovery`` (the
survivors' masks toward dropped clients left in the aggregate) and
``sgd_bf16`` (local SGD's forward and backward passes in bfloat16, params,
update, aggregation and server update in float32: what the default matmul
precision of a TPU does to the convolutions).

Every round also records each client's step-1 loss (at the round-start
params; the probe), read before a BatchNorm model's rounding grows over the
later steps.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

# the counter-based mask streams' constants (murmur3 avalanche, salts)
DH_PRIME = (1 << 61) - 1
DH_GEN = 5
IDX_SALT = 0x9E3779B9
VAL_SALT = 0x85EBCA6B
LEAF_SALT = 0xA511E9B3
FAULTS = ("half_batch", "lost_upload", "no_recovery", "sgd_bf16")
NP_DTYPES = {"float32": np.float32, "bfloat16": ml_dtypes.bfloat16}


# ---------------------------------------------------------- Eq. 1 schedule
def eq1_ks(thgs: dict, sizes: list[int]) -> list[int]:
    """Per-leaf top-k: rate s_1 = s0, s_i = max(alpha * s_{i-1}, s_min)
    in leaf order, k = ceil(size * s) snapped to ``k_levels`` geometric
    levels between 1 and the leaf size."""
    ks, rate = [], thgs["s0"]
    for i, size in enumerate(sizes):
        if i:
            rate = max(rate * thgs["alpha"], thgs["s_min"])
        k = max(1, math.ceil(size * rate))
        if k >= size:
            k = size
        elif k > 1:
            levels = thgs["k_levels"]
            pos = round(math.log(k) / math.log(size) * levels) / levels
            k = max(1, min(size, int(round(size ** pos))))
        ks.append(min(k, size))
    return ks


def k_mask(secagg: dict, size: int, n_clients: int) -> int:
    """Eq. 4: mask-support slots per pair and leaf."""
    if not secagg["enabled"] or n_clients < 2:
        return 0
    return max(1, int(size * secagg["mask_ratio"] / n_clients))


# ------------------------------------------------------------- pair masks
def _mix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x7FEB352D)
    x ^= x >> np.uint32(15)
    x *= np.uint32(0x846CA68B)
    x ^= x >> np.uint32(16)
    return x


def _dh_private(seed: int, u: int) -> int:
    h = hashlib.sha256(f"dhpriv:{seed}:{u}".encode()).digest()
    return int.from_bytes(h[:16], "little") % (DH_PRIME - 2) + 1


def pair_seed(sa_seed: int, a: int, b: int, round_t: int) -> int:
    """The round's uint32 counter seed of the unordered pair (a, b)."""
    secret = pow(pow(DH_GEN, _dh_private(sa_seed, b), DH_PRIME),
                 _dh_private(sa_seed, a), DH_PRIME)
    h = hashlib.sha256(f"mask:{secret}:{round_t}".encode()).digest()
    return int.from_bytes(h[:4], "little")


def pair_mask(seed: int, leaf_id: int, k: int, size: int, p: float,
              q: float) -> tuple[np.ndarray, np.ndarray]:
    """(indices, values before the pair's sign) of one pair's mask on one
    leaf: ``k`` counter draws, values uniform on ``[p, p + q)``."""
    leaf = _mix32(np.array([leaf_id + LEAF_SALT], np.uint64)
                  .astype(np.uint32))
    s = _mix32(np.array([seed], np.uint32) ^ leaf)
    ctr = np.arange(k, dtype=np.uint32)
    idx = _mix32(_mix32(s ^ np.uint32(IDX_SALT)) + ctr) % np.uint32(size)
    u = (_mix32(_mix32(s ^ np.uint32(VAL_SALT)) + ctr) >> np.uint32(8)
         ).astype(np.float32) / np.float32(2**24)
    return idx.astype(np.int64), (np.float32(p) + np.float32(q) * u)


# -------------------------------------------------------------- local SGD
def _cross_entropy(forward, params, x, y):
    logp = jax.nn.log_softmax(forward(params, x))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


@partial(jax.jit, static_argnames=("forward", "lr", "half", "compute"))
def _local_sgd(params, xs, ys, *, forward, lr, half, compute):
    """Plain SGD over the steps of ``xs``/``ys``: (delta, mean loss, the
    first step's loss). ``compute`` names a dtype that the loss casts params
    and inputs to (``sgd_bf16``), or is None."""
    def loss_fn(p, x, y):
        if compute is not None:
            p = jax.tree_util.tree_map(lambda a: a.astype(compute), p)
            x = x.astype(compute)
        return _cross_entropy(forward, p, x, y)

    def step(p, batch):
        x, y = batch
        if half:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), loss

    new, losses = jax.lax.scan(step, params, (xs, ys))
    losses = losses.astype(jnp.float32)
    delta = jax.tree_util.tree_map(jnp.subtract, new, params)
    return delta, jnp.mean(losses), losses[0]


def _clients_sgd(model, p_tree, cohort, batches, jdt, lr, half,
                 compute=None):
    """Every cohort client's local SGD from ``p_tree``: (flat deltas per
    client, mean loss per client, step-1 loss per client)."""
    deltas, losses, step1 = {}, [], []
    for c in cohort:
        xs, ys = batches[c]
        d, loss, first = _local_sgd(
            p_tree, jnp.asarray(xs, jdt), jnp.asarray(ys),
            forward=model.forward, lr=lr, half=half, compute=compute)
        deltas[c] = [np.asarray(x).reshape(-1)
                     for x in jax.tree_util.tree_leaves(d)]
        losses.append(float(loss))
        step1.append(float(first))
    return deltas, losses, step1


# ------------------------------------------------------------ aggregation
def top_k(mag: np.ndarray, k: int) -> np.ndarray:
    """The indices of the ``k`` largest entries of ``mag``; where entries
    tie at the k-th place, the lowest indices are taken, as ``lax.top_k``
    documents for the program's encode."""
    kth = np.partition(mag, mag.size - k)[mag.size - k]
    above = np.flatnonzero(mag > kth)
    return np.concatenate([above,
                           np.flatnonzero(mag == kth)[:k - above.size]])


def _aggregate(params, res, deltas, cohort, dropped, ks, config, seeds,
               ndt, fault):
    """The server side of one round, in place on flat leaves: each client's
    top-k on ``residual + delta`` and its mask support, the survivors' sum
    over the number of survivors, the server update into ``params`` and the
    residual write-back into ``res`` (a dropped client keeps its whole
    accumulator)."""
    sec = config["secagg"]
    acc_dt = np.float64 if ndt is np.float32 else ndt
    survivors = [c for c in cohort if c not in dropped]
    lost = survivors[0] if fault == "lost_upload" else None
    for leaf_id, size in enumerate(int(p.size) for p in params):
        km = k_mask(sec, size, len(cohort))
        k = min(ks[leaf_id], size)
        agg = np.zeros(size, acc_dt)
        for c in cohort:
            acc = (res[c][leaf_id] + deltas[c][leaf_id]).astype(ndt)
            if c in dropped:
                res[c][leaf_id] = acc
                continue
            support = [top_k(np.abs(acc.astype(np.float32)), k)]
            for peer in cohort:
                if peer == c or not km:
                    continue
                idx, u = pair_mask(seeds[min(c, peer), max(c, peer)],
                                   leaf_id, km, size, sec["p"], sec["q"])
                support.append(idx)
                if fault == "no_recovery" and peer in dropped:
                    np.add.at(agg, idx, (u if c < peer else -u)
                              .astype(acc_dt))
            sup = np.unique(np.concatenate(support))
            if c != lost:
                agg[sup] += acc[sup].astype(acc_dt)
            acc[sup] = 0
            res[c][leaf_id] = acc
        update = (agg / len(survivors)).astype(ndt)
        params[leaf_id] = (params[leaf_id]
                           + ndt(config["server_lr"]) * update).astype(ndt)


def _pair_seeds(sa_seed: int, cohort: list, round_t: int) -> dict:
    return {(a, b): pair_seed(sa_seed, a, b, round_t)
            for i, a in enumerate(cohort) for b in cohort[i + 1:]}


# ------------------------------------------------------------------ rounds
@dataclasses.dataclass
class Outputs:
    """What the comparison reads, per side: each round's mean client loss,
    the flat params before round 1, every client's residual after the last
    compared round (name -> array, stacked over clients), and one
    ``record`` per compared round."""

    losses: list
    params0: dict
    residuals: dict
    rounds: list


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def record(names, shapes, cohort, deltas, params, res, step1) -> dict:
    """One compared round as the comparison reads it, in float32: the
    (sorted) cohort's local-SGD ``deltas`` and residuals after the round
    (name -> [C, ...]), the params after it (name -> array), and each
    client's step-1 loss (``step1``, [C])."""
    def stacked(per_client):
        return {n: np.stack([per_client[c][i].astype(np.float32).reshape(s)
                             for c in cohort])
                for i, (n, s) in enumerate(zip(names, shapes))}

    return {"deltas": stacked(deltas),
            "params": {n: p.astype(np.float32).reshape(s)
                       for n, p, s in zip(names, params, shapes)},
            "residuals": stacked(res),
            "step1": np.asarray(step1, np.float64)}


def run(model, config: dict, traffic: dict, seed: int, sa_seed: int,
        rounds: list, dtype: str = "float32",
        fault: str | None = None) -> Outputs:
    """Follow ``rounds`` (each ``{"cohort", "dropped", "batches"}`` with
    ``batches[c] = (x [steps, batch, ...], y [steps, batch])``) from the
    seed's initial weights."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    jdt = jnp.dtype(dtype)
    ndt = NP_DTYPES[dtype]
    tree = model.init(jax.random.key(seed), jdt)
    flat, treedef = jax.tree_util.tree_flatten(tree)
    names = leaf_names(tree)
    shapes = [x.shape for x in flat]
    ks = eq1_ks(config["thgs"], [int(x.size) for x in flat])
    params = [np.asarray(x).reshape(-1) for x in flat]
    params0 = {n: p.astype(np.float32).reshape(s)
               for n, p, s in zip(names, params, shapes)}
    res = {c: [np.zeros(p.size, ndt) for p in params]
           for c in range(traffic["n_clients"])}
    losses, records = [], []
    for r, rd in enumerate(rounds):
        cohort = sorted(int(c) for c in rd["cohort"])
        p_tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(p.reshape(s)) for p, s in zip(params,
                                                                  shapes)])
        deltas, round_losses, step1 = _clients_sgd(
            model, p_tree, cohort, rd["batches"], jdt, traffic["local_lr"],
            fault == "half_batch",
            "bfloat16" if fault == "sgd_bf16" else None)
        losses.append(float(np.mean(round_losses)))
        _aggregate(params, res, deltas, cohort,
                   {int(c) for c in rd["dropped"]}, ks, config,
                   _pair_seeds(sa_seed, cohort, r), ndt, fault)
        records.append(record(names, shapes, cohort, deltas, params, res,
                              step1))
    return Outputs(
        losses=losses, params0=params0,
        residuals={n: np.stack([res[c][i].astype(np.float32).reshape(s)
                                for c in sorted(res)])
                   for i, (n, s) in enumerate(zip(names, shapes))},
        rounds=records)


def forced(model, config: dict, traffic: dict, sa_seed: int, rounds: list,
           other: Outputs) -> list:
    """Teacher-forced mode: each compared round again in float32 from the
    state that ``other`` (the program, or the control or a fault put in its
    place) had at the round's start, on the same inputs. Local SGD runs
    from ``other``'s params; the aggregation runs on ``other``'s own deltas
    and residuals. So no difference carries over from an earlier round or
    from local SGD into the aggregation, and each stage is read before
    rounding has grown through later steps; the step-1 loss is read at
    ``other``'s params too. One ``record`` per round."""
    shapes = [x.shape for x in other.params0.values()]
    treedef = jax.tree_util.tree_structure(
        jax.eval_shape(model.init, jax.random.key(0)))
    names = list(other.params0)
    ks = eq1_ks(config["thgs"], [int(np.prod(s)) for s in shapes])

    def flat(leaves: dict, i=None):
        return [np.asarray(leaves[n] if i is None else leaves[n][i],
                           np.float32).reshape(-1) for n in names]

    params = flat(other.params0)
    res = {c: [np.zeros(p.size, np.float32) for p in params]
           for c in range(traffic["n_clients"])}
    out = []
    for r, (rd, taken) in enumerate(zip(rounds, other.rounds)):
        cohort = sorted(int(c) for c in rd["cohort"])
        p_tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(p.reshape(s)) for p, s in zip(params,
                                                                  shapes)])
        deltas, _, step1 = _clients_sgd(
            model, p_tree, cohort, rd["batches"], jnp.float32,
            traffic["local_lr"], False)
        after = list(params)
        res_after = {c: list(res[c]) for c in cohort}
        _aggregate(after, res_after,
                   {c: flat(taken["deltas"], i) for i, c in enumerate(cohort)},
                   cohort, {int(c) for c in rd["dropped"]}, ks, config,
                   _pair_seeds(sa_seed, cohort, r), np.float32, None)
        out.append(record(names, shapes, cohort, deltas, after, res_after,
                          step1))
        params = flat(taken["params"])
        for i, c in enumerate(cohort):
            res[c] = flat(taken["residuals"], i)
    return out
