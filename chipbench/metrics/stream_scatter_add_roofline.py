"""``stream_scatter_add_roofline``: the decode kernel's share of its
roofline, in %.

The work is what any scatter-add of the round's streams needs: every slot
that the round really decoded read once (an int32 index and an f32 value),
and the dense f32 leaf written once. Slots per leaf and round: each of the C
cohort clients' ``k + C * k_mask`` (the stream as encoded, the gated self
slot included) plus, in a round with dropped clients, the ``C * C *
k_mask`` recovery slots. The adds are one per slot, so the bound is the
bytes over the HBM bandwidth. The kernel's time is the summed device time
of its operations in the window, found by ``OP_NAMES``.
"""

# the names the kernel's operations carry in a TPU trace
OP_NAMES = ("stream_scatter_add",)


def work_bytes(rounds) -> int:
    total = 0
    for r in rounds:
        C = r["n_clients"]
        dropped = r["n_clients"] != r["n_survivors"]
        for k, km, size in zip(r["ks"], r["k_masks"], r["leaf_sizes"]):
            slots = C * (k + C * km) + (C * C * km if dropped else 0)
            total += 8 * slots + 4 * size
    return total


def read(view):
    ops = view.ops_named(OP_NAMES)
    if not ops or not view.facts["rounds"]:
        return None
    kernel_s = sum(e - s for s, e, _ in ops) * 1e-9 / len(view.devices)
    bound_s = (work_bytes(view.facts["rounds"]) / len(view.devices)
               / view.facts["peaks"]["hbm_bytes_per_s"])
    return 100.0 * bound_s / kernel_s
