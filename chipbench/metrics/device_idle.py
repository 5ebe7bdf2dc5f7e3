"""``device_idle``: the share of the traced window in which no operation
ran on the device, 1 - (union of operation intervals / window), averaged
over the chips, in %."""


def read(view):
    if view.window_s <= 0 or not view.devices:
        return None
    return 100.0 * (1.0 - view.busy_s / view.window_s)
