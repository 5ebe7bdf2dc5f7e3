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
  error-feedback accumulator ``residual + delta``;
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

``dtype='bfloat16'`` computes the same rounds in bfloat16 throughout: the
control, which the comparison must refuse. ``fault`` plants one of the
faults that the comparison must catch: ``half_batch`` (the loss of each
local step over the first half of its batch), ``lost_upload`` (one
survivor's values left out of the aggregate) and ``no_recovery`` (the
survivors' masks toward dropped clients left in the aggregate).
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
FAULTS = ("half_batch", "lost_upload", "no_recovery")
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


@partial(jax.jit, static_argnames=("forward", "lr", "half"))
def _local_sgd(params, xs, ys, *, forward, lr, half):
    def step(p, batch):
        x, y = batch
        if half:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        loss, g = jax.value_and_grad(partial(_cross_entropy, forward))(
            p, x, y)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), loss

    new, losses = jax.lax.scan(step, params, (xs, ys))
    delta = jax.tree_util.tree_map(jnp.subtract, new, params)
    return delta, jnp.mean(losses.astype(jnp.float32))


# ------------------------------------------------------------------ rounds
@dataclasses.dataclass
class Outputs:
    """What the comparison reads, per side: each round's mean client loss,
    the flat params before round 1, after round 1 and after the last
    compared round, and every client's residual after it (name -> array,
    stacked over clients)."""

    losses: list
    params0: dict
    params1: dict
    params_n: dict
    residuals: dict


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


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
    acc_dt = np.float64 if dtype == "float32" else ndt
    tree = model.init(jax.random.key(seed), jdt)
    flat, treedef = jax.tree_util.tree_flatten(tree)
    names = leaf_names(tree)
    sizes = [int(x.size) for x in flat]
    shapes = [x.shape for x in flat]
    ks = eq1_ks(config["thgs"], sizes)
    sec = config["secagg"]
    lr, server_lr = traffic["local_lr"], config["server_lr"]
    params = [np.asarray(x).reshape(-1) for x in flat]
    params0 = {n: p.astype(np.float32).reshape(s)
               for n, p, s in zip(names, params, shapes)}
    res = {c: [np.zeros(s, ndt) for s in sizes]
           for c in range(traffic["n_clients"])}
    losses, params1 = [], None
    for r, rd in enumerate(rounds):
        cohort = sorted(int(c) for c in rd["cohort"])
        dropped = {int(c) for c in rd["dropped"]}
        survivors = [c for c in cohort if c not in dropped]
        C = len(cohort)
        p_tree = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(p.reshape(s)) for p, s in zip(params,
                                                                  shapes)])
        deltas, round_losses = {}, []
        for c in cohort:
            xs, ys = rd["batches"][c]
            d, loss = _local_sgd(p_tree, jnp.asarray(xs, jdt),
                                 jnp.asarray(ys), forward=model.forward,
                                 lr=lr, half=fault == "half_batch")
            deltas[c] = [np.asarray(x).reshape(-1)
                         for x in jax.tree_util.tree_leaves(d)]
            round_losses.append(float(loss))
        losses.append(float(np.mean(round_losses)))
        seeds = {(a, b): pair_seed(sa_seed, a, b, r)
                 for i, a in enumerate(cohort) for b in cohort[i + 1:]}
        lost = survivors[0] if fault == "lost_upload" else None
        for leaf_id, size in enumerate(sizes):
            km = k_mask(sec, size, C)
            k = min(ks[leaf_id], size)
            agg = np.zeros(size, acc_dt)
            for c in cohort:
                acc = (res[c][leaf_id] + deltas[c][leaf_id]).astype(ndt)
                if c in dropped:
                    res[c][leaf_id] = acc
                    continue
                mag = np.abs(acc.astype(np.float32))
                support = [np.argpartition(-mag, k - 1)[:k]]
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
                               + ndt(server_lr) * update).astype(ndt)
        if r == 0:
            params1 = {n: p.astype(np.float32).reshape(s)
                       for n, p, s in zip(names, params, shapes)}
    return Outputs(
        losses=losses, params0=params0, params1=params1,
        params_n={n: p.astype(np.float32).reshape(s)
                  for n, p, s in zip(names, params, shapes)},
        residuals={n: np.stack([res[c][i].astype(np.float32).reshape(s)
                                for c in sorted(res)])
                   for i, (n, s) in enumerate(zip(names, shapes))})
