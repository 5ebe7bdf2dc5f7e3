"""``round_mfu``: the whole round's share of the chip's peak: the model
FLOPs that local SGD needs per round (forward and backward, 3x the forward
pass, for every cohort client's steps x batch, counted from the shapes by
the configuration's ``forward_flops``) over the traced window's time per
round times the peak FLOP/s of the chips used, in %."""


def read(view):
    if view.n_rounds == 0 or view.window_s <= 0 or not view.devices:
        return None
    per_round_s = view.window_s / view.n_rounds
    peak = view.facts["peaks"]["flops_per_s"] * len(view.devices)
    return 100.0 * view.facts["train_flops_per_round"] / (per_round_s * peak)
