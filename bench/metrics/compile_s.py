"""compile_s: seconds of backend compilation (or compile-cache
retrieval) in set-up, from JAX's monitoring events. Moves setup_s."""


def read(rec):
    return rec["setup"]["compile_s"]
