"""hbm_peak_gib.train: peak_bytes_in_use of the fullest device after the
window, in GiB. Moves train_tokens_per_s (memory bounds the batch and
the schedule's stash)."""


def read(rec):
    b = rec["memory_peak_bytes"]
    return None if b is None else b / 2**30
