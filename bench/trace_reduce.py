"""Profiler traces: capture a window, and reduce it to device time.

A trace is read into plain events ``[plane, line, name, start_ns,
dur_ns]`` (``load``), keeping the device planes' op lines and the host
spans the benchmark opened (names starting "bench."). ``reduce`` turns
those events into per-device busy time, per-kernel device time, the time
collectives ran with no compute beside them, and the breakdown the
result line carries. The test reduces a small recorded trace with it.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|"
                        r"collective-permute|all-to-all|send|recv",
                        re.IGNORECASE)
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event: str) -> str:
    """An op's name without its instance number. TPU traces name an op
    event by its HLO text ("%paged_attention.80 = bf16[...]
    custom-call(...)"): the name is what precedes " = "."""
    return _SUFFIX.sub("", event.split(" = ", 1)[0].lstrip("%"))


@contextlib.contextmanager
def capture(logdir: str):
    """Trace everything inside the block into ``logdir``."""
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(logdir: str) -> list:
    """The newest trace under ``logdir`` as plain events."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    events.append([plane.name, line.name, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)])
    return events


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _minus(a, b):
    """Merged intervals ``a`` without merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _parents(ops) -> set:
    """Indices of ops that enclose another op of the same device (control
    flow such as "while" or "cond", and wrappers): their time is their
    children's, so the breakdown leaves them out."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    stack, parents = [], set()
    for i in order:
        s, e = ops[i][0], ops[i][1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            parents.add(stack[-1])
        stack.append(i)
    return parents


def reduce(events: list, kernels: dict | None = None) -> dict:
    """Reduce a window's events.

    ``kernels`` maps a kernel's key to the op names (``op_name``) of its
    device ops. Returns the window's length, each device's busy and
    exposed-collective seconds, per-kernel seconds and call counts
    (averaged over devices), and the breakdown: the ops that took most
    device time (ops that enclose others left out), and the longest idle
    gaps named by the innermost host span open at their middle."""
    kernels = kernels or {}
    host = [ev for ev in events if not ev[0].startswith("/device:")]
    win = [ev for ev in host if ev[2] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo = min(ev[3] for ev in win)
    hi = max(ev[3] + ev[4] for ev in win)
    spans = sorted(((ev[3], ev[3] + ev[4], ev[2]) for ev in host
                    if ev[2] != WINDOW_SPAN), key=lambda x: (x[0], -x[1]))

    planes: dict[str, list] = {}
    for ev in events:
        if ev[0].startswith("/device:") and ev[1] == OPS_LINE:
            s, e = _clip(ev[3], ev[3] + ev[4], lo, hi)
            if e > s:
                planes.setdefault(ev[0], []).append((s, e, op_name(ev[2])))
    if not planes:
        raise ValueError("no device op ran in the traced window")

    per_device, op_time, gaps = {}, {}, []
    kern = {k: {"seconds": 0.0, "calls": 0} for k in kernels}
    for plane, ops in sorted(planes.items()):
        busy = _union([[s, e] for s, e, _ in ops])
        coll = _union([[s, e] for s, e, n in ops if COLLECTIVE.search(n)])
        comp = _union([[s, e] for s, e, n in ops
                       if not COLLECTIVE.search(n)])
        per_device[plane] = {
            "busy_s": _length(busy) / 1e9,
            "collective_exposed_s": _length(_minus(coll, comp)) / 1e9}
        parents = _parents(ops)
        for i, (s, e, n) in enumerate(ops):
            if i not in parents:
                op_time[n] = op_time.get(n, 0.0) + (e - s) / 1e9
            for k, names in kernels.items():
                if n in names:
                    kern[k]["seconds"] += (e - s) / 1e9
                    kern[k]["calls"] += 1
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) // 2))
    n_dev = len(planes)
    for k in kern:
        kern[k] = {"seconds": kern[k]["seconds"] / n_dev,
                   "calls": kern[k]["calls"] / n_dev}

    def label(t):
        inner = None
        for s, e, name in spans:
            if s > t:
                break
            if e >= t:
                inner = name
        return inner or "no bench span"

    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": per_device,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n_dev,
        "collective_exposed_s": sum(d["collective_exposed_s"]
                                    for d in per_device.values()) / n_dev,
        "kernels": kern,
        "breakdown": {
            "device_ops": [[n, t / n_dev] for n, t in sorted(
                op_time.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [[label(mid), g / 1e9] for g, mid in gaps[:10]],
        },
    }
