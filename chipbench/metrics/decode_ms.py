"""``decode_ms``: device milliseconds per round of the server decode
(``streams.decode_leaf_batch``: the fused scatter-add and the Bonawitz
recovery streams), one program per leaf: the summed device time of the
modules whose name holds ``decode_leaf_batch``, over the traced window's
rounds."""

PATTERN = "decode_leaf_batch"


def read(view):
    if view.n_rounds == 0 or not view.devices:
        return None
    return view.module_s(PATTERN) * 1e3 / view.n_rounds
