"""``encode_ms``: device milliseconds per round of the THGS encode
(``streams.encode_leaf_batch``: top-k, first-occurrence gate, pair-mask
streams), one program per leaf: the summed device time of the modules whose
name holds ``encode_leaf_batch``, over the traced window's rounds."""

PATTERN = "encode_leaf_batch"


def read(view):
    if view.n_rounds == 0 or not view.devices:
        return None
    return view.module_s(PATTERN) * 1e3 / view.n_rounds
