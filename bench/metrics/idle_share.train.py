"""idle_share.train: 1 - the device's busy time (the union of its ops)
over the traced window, averaged over the chips. Moves train_tokens_per_s."""


def read(rec):
    tr = rec["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
