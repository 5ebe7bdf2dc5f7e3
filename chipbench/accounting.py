"""The paper's communication accounting (Eq. 6-8, "paper bits").

A sparse element on the wire is a 64-bit value plus a 32-bit index (96
bits); a dense element is a 64-bit value. A client uploads, per leaf, its
``k`` top-k slots plus ``k_mask`` mask-support slots toward each of its
``C - 1`` peers; only survivors' uploads arrive. Dense FedAvg would have
every participant upload the whole model. The inputs are the facts each
round records (per-leaf ``ks`` and ``k_masks``, cohort and survivor
counts, model size).
"""
from __future__ import annotations

SPARSE_BITS = 64 + 32
DENSE_BITS = 64


def upload_bits(ks, k_masks, n_clients: int, n_survivors: int) -> int:
    """Eq. 6-7: the round's sparse upload, summed over survivors."""
    slots = sum(ks) + max(n_clients - 1, 0) * sum(k_masks)
    return n_survivors * slots * SPARSE_BITS


def dense_bits(model_size: int, n_clients: int) -> int:
    """Dense FedAvg's upload for the same cohort."""
    return n_clients * model_size * DENSE_BITS


def upload_vs_dense(rounds) -> float:
    """Sparse upload bits over dense FedAvg's, over ``rounds`` (dicts with
    ``ks``, ``k_masks``, ``n_clients``, ``n_survivors``, ``model_size``)."""
    up = sum(upload_bits(r["ks"], r["k_masks"], r["n_clients"],
                         r["n_survivors"]) for r in rounds)
    dense = sum(dense_bits(r["model_size"], r["n_clients"]) for r in rounds)
    return up / dense
