"""tick_ms.serve: mean wall time of the ServeEngine.step() calls that
did work in the window (the benchmark drives step() itself). Moves
itl_p95_ms."""


def read(rec):
    t = rec["tick_s"]
    return 1e3 * sum(t) / len(t) if t else None
