"""``upload_vs_dense``: the paper's headline count, the window's sparse
upload bits over dense FedAvg's for the same rounds, under the paper's bit
accounting (Eq. 6-8, ``accounting.py``), from each traced round's
recorded facts (per-leaf ks and k_masks, cohort, survivors)."""

from chipbench import accounting


def read(view):
    if not view.facts["rounds"]:
        return None
    return accounting.upload_vs_dense(view.facts["rounds"])
