"""The Bonawitz-style secure-aggregation round protocol (paper Alg. 2).

One :class:`RoundProtocol` instance is one round's control plane, in four
phases (Bonawitz et al. 2017, adapted to the paper's sparse masks):

0. **Advertise keys** — every participant derives a DH key pair
   (masks.dh_private/dh_public) and publishes the public key.
1. **Share keys** — every participant Shamir-shares its *private* key among
   the cohort with threshold ``t = sa.t_for(C)`` (shamir.py). One share per
   peer crosses the wire (``C·(C-1)`` uploads + the server's relay), which
   core/costs accounts as ``share_upload_bits``/``share_download_bits``.
2. **Masked input collection** — the data plane: ``pair_seed_matrix`` hands
   the per-pair uint32 counter seeds to the batched encode
   (streams.encode_leaf_batch with ``pair_seeds``), which generates every
   pair mask of the round in one fused kernel/oracle pass.
3. **Unmasking** — the server collects the survivor set; for each dropped
   client it obtains ``t`` survivors' shares of that client's private key
   (``recovery_upload_bits``), reconstructs the key, re-derives the
   survivor→dropped pair seeds and cancels the now-unpaired masks
   (streams.dropout_cancel_streams_seeded). Fewer than ``t`` survivors ⇒
   :class:`ThresholdError` — the round aborts, exactly the real protocol's
   failure mode.

Hierarchical aggregation (DESIGN.md §13) needs **no change** to this
protocol: the tree's sub-aggregators are index-range shards of the dense
buffer, and pair masks cancel per-position — both endpoints of a pair mask
target the same positions, so their contributions route to the same
sub-aggregator and cancel inside its partial regardless of which clients the
pair spans. Pair seeds stay all-pairs over the cohort; dropout recovery
streams route by range exactly like client streams.

Threat-model boundary (DESIGN.md §10): DH and Shamir arithmetic are real
(modular exponentiation over GF(2^61-1); polynomial shares), their
*parameters* are toy and their randomness is derived deterministically from
the federation seed so runs reproduce. The reconstruction path genuinely
flows through share recombination — tests assert the recovered key and the
regenerated masks are bit-identical to the encode-time originals.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import masks
from repro.core.types import SecureAggConfig
from repro.secagg import shamir


class ThresholdError(RuntimeError):
    """Survivors fell below the Shamir threshold — the round cannot unmask."""


@dataclasses.dataclass(frozen=True)
class RoundProtocol:
    """One round's key agreement + secret sharing + recovery state.

    Build with :meth:`setup`; hand ``pair_seed_matrix()`` to the encode and,
    on dropout, ``recover_seeds()`` to the decode. ``t`` is the Shamir
    threshold; ``publics`` the advertised DH public keys; ``shares[u]`` maps
    holder point ``v + 1`` to holder ``v``'s share of ``u``'s private key.
    """

    sa: SecureAggConfig
    participants: tuple
    round_t: int
    t: int
    publics: Mapping[int, int]
    shares: Mapping[int, Mapping[int, int]]
    privs: Mapping[int, int]

    @classmethod
    def setup(cls, sa: SecureAggConfig, participants: Sequence[int],
              round_t: int) -> "RoundProtocol":
        """Phases 0-1: advertise key pairs, Shamir-share the private keys."""
        n = len(participants)
        with TraceAnnotation("fl.secagg.setup", shares=n * (n - 1)):
            parts = tuple(sorted(int(c) for c in participants))
            if len(set(parts)) != len(parts):
                raise ValueError(f"duplicate participant ids: {parts}")
            if len(parts) < 2:
                raise ValueError("secure aggregation needs >= 2 participants")
            t = sa.t_for(len(parts))
            publics = {}
            shares = {}
            privs = {}
            points = [u + 1 for u in parts]
            for u in parts:
                x_u = masks.dh_private(sa.seed, u)
                privs[u] = x_u
                publics[u] = masks.dh_public(x_u)
                shares[u] = shamir.share(
                    x_u, points, t, tag=f"{sa.seed}:{u}:{round_t}")
            return cls(sa=sa, participants=parts, round_t=round_t, t=t,
                       publics=publics, shares=shares, privs=privs)

    # ------------------------------------------------------------ data plane
    def pair_seed_matrix(self):
        """Phase 2 inputs: uint32 [C, C] counter seeds + Bonawitz signs.

        Derived from THIS protocol's key state (``privs``/``publics``) via
        masks.seed_matrix_from_keys — exactly the derivation
        ``recover_seeds`` replays from the Shamir-reconstructed key — so
        encode masks and recovery masks agree for any ``RoundProtocol``,
        including one built with keys that are not the ``sa.seed``-derived
        defaults (test doubles, a future CSPRNG setup). For ``setup()``-built
        instances the result is bit-identical to ``streams.pair_seed_matrix``
        (the protocol-free engine entry point).
        """
        parts = self.participants
        with TraceAnnotation("fl.secagg.pair_seeds"):
            return masks.seed_matrix_from_keys(
                parts, [self.privs[u] for u in parts],
                [self.publics[u] for u in parts], self.round_t)

    # -------------------------------------------------------------- recovery
    def recover_seeds(self, survivors: Sequence[int],
                      dropped: Sequence[int]):
        """Phase 3: reconstruct dropped clients' keys, re-derive pair seeds.

        Returns a uint32 [C, C] matrix filled only at survivor↔dropped
        entries (everything else 0 — the decode's ``alive`` gate zeroes those
        pairs anyway). Raises :class:`ThresholdError` when the survivor set
        is smaller than ``t``, and ValueError when a reconstructed key does
        not match the advertised public key (a corrupted share).
        """
        with TraceAnnotation("fl.secagg.recover",
                             shares=self.t * len(dropped)):
            surv = sorted(int(c) for c in survivors)
            drop = sorted(int(c) for c in dropped)
            known = set(self.participants)
            if not set(surv) <= known or not set(drop) <= known:
                raise ValueError(
                    "survivors/dropped must be round participants")
            if set(surv) & set(drop):
                raise ValueError("a client cannot both survive and drop")
            if len(surv) < self.t:
                raise ThresholdError(
                    f"{len(surv)} survivors < threshold t={self.t}: "
                    "the dropped clients' masks cannot be reconstructed")
            pos = {u: i for i, u in enumerate(self.participants)}
            C = len(self.participants)
            seeds = np.zeros((C, C), np.uint32)
            for d in drop:
                # the server queries exactly t survivors for their shares of
                # d's key — that is the recovery traffic
                # costs.recovery_upload_bits charges
                pts = {v + 1: self.shares[d][v + 1] for v in surv[:self.t]}
                x_d = shamir.reconstruct(pts)
                if masks.dh_public(x_d) != self.publics[d]:
                    raise ValueError(
                        f"reconstructed key of client {d} fails the "
                        "public-key check — corrupted share?")
                for s in surv:
                    secret = pow(self.publics[s], x_d, masks.DH_PRIME)
                    sd = masks.seed_from_secret(secret, self.round_t)
                    seeds[pos[s], pos[d]] = sd
                    seeds[pos[d], pos[s]] = sd
            return jnp.asarray(seeds)

    # ------------------------------------------------------------ accounting
    @property
    def n_phase1_shares(self) -> int:
        """Shares crossing the wire in phase 1 (self-share stays local)."""
        C = len(self.participants)
        return C * (C - 1)

    def n_recovery_shares(self, n_dropped: int) -> int:
        """Shares uploaded by survivors to unmask ``n_dropped`` clients."""
        return self.t * n_dropped
