"""``local_sgd_ms``: device milliseconds per round of local SGD
(``fedavg.batched_client_update``, every cohort client's steps in one
vmapped program): the summed device time of the modules whose name holds
``batched_client_update``, over the traced window's rounds."""

PATTERN = "batched_client_update"


def read(view):
    if view.n_rounds == 0 or not view.devices:
        return None
    return view.module_s(PATTERN) * 1e3 / view.n_rounds
