"""idle_share.serve: 1 - the device's busy time (the union of its ops)
over the traced window, averaged over the chips. Moves itl_p95_ms."""


def read(rec):
    tr = rec["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
