"""Reduction of a JAX profiler trace to device busy time, kernel time
and idle gaps.

The benchmark traces its measured window inside a host span named
``window`` and wraps each of its calls into the program's layers in a
span of the layer's name (``SPANS``; they do not overlap).  From the
``.xplane.pb`` the profiler writes, this module takes

* the window: the ``window`` span on the host planes;
* device busy time: the union of the intervals in which an operation
  of a device's ``XLA Ops`` line runs, clipped to the window, averaged
  over the devices traced;
* time per device operation, by name (a kernel is found by its name);
* idle gaps: the window minus the busy union of the first device, each
  second of it attributed to the host span that covered it, or to
  ``no span``.

The file is read with the ``XSpace`` protocol buffer, and the event
arrays are reduced with numpy: an engine window holds millions of
device operations.  A trace with no device plane, no op line or no
window yields ``None``: there is then nothing to read, and no metric is
made up.
"""
from __future__ import annotations

import glob
import importlib.util
import os
import re

import numpy as np

__all__ = ["SPANS", "WINDOW_SPAN", "find_xplane", "reduce_trace",
           "reduce_file", "breakdown", "op_seconds"]

WINDOW_SPAN = "window"
SPANS = ("sample_cohort", "compute_cohort", "transmit", "offer_uploads",
         "close_round", "apply_round", "close_digest", "evaluate",
         "train_step", "run_scheduled")
OP_LINE = "XLA Ops"
NO_SPAN = "no span"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(log_dir: str) -> str | None:
    """Newest ``*.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


class _Ops:
    """One device's operations: start and end (ns) and a name index."""

    def __init__(self, start, end, op, names):
        self.start = np.asarray(start, np.float64)
        self.end = np.asarray(end, np.float64)
        self.op = np.asarray(op, np.int64)
        self.names = list(names)


def reduce_trace(planes, spans=SPANS) -> dict | None:
    """``planes``: iterable of objects with ``name`` and ``lines``, each
    line with ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``).

    → ``busy_s``, ``window_s``, ``devices``, ``op_s`` (seconds per
    operation name, averaged over devices) and ``idle_s`` (idle
    seconds of the first device by host span), or ``None``.
    """
    host, devices = [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            host += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                     for line in plane.lines for ev in line.events
                     if ev.name == WINDOW_SPAN or ev.name in spans]
        elif _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                names: dict[str, int] = {}
                evs = list(line.events)
                devices.append(_Ops(
                    [e.start_ns for e in evs],
                    [e.start_ns + e.duration_ns for e in evs],
                    [names.setdefault(e.name, len(names)) for e in evs],
                    names))
    return _reduce(host, devices)


def _xplane_pb2():
    """The ``XSpace`` message module shipped with the installed
    TensorFlow, loaded by path so that TensorFlow itself is not
    imported."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("reading a profiler trace needs the XSpace "
                          "protocol buffer of the installed tensorflow")
    path = os.path.join(spec.submodule_search_locations[0], "tsl",
                        "profiler", "protobuf", "xplane_pb2.py")
    mspec = importlib.util.spec_from_file_location("_bench_xplane_pb2", path)
    mod = importlib.util.module_from_spec(mspec)
    mspec.loader.exec_module(mod)
    return mod


def reduce_file(path: str, spans=SPANS) -> dict | None:
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    want = set(spans) | {WINDOW_SPAN}
    host, devices = [], []
    for plane in space.planes:
        meta = plane.event_metadata
        if plane.name.startswith("/host:"):
            ids = {i for i, m in meta.items() if m.name in want}
            for line in plane.lines:
                base = line.timestamp_ns
                for ev in line.events:
                    if ev.metadata_id in ids:
                        a = base + ev.offset_ps * 1e-3
                        host.append((a, a + ev.duration_ps * 1e-3,
                                     meta[ev.metadata_id].name))
        elif _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                n = len(line.events)
                raw = np.fromiter(
                    (x for ev in line.events
                     for x in (ev.offset_ps, ev.duration_ps, ev.metadata_id)),
                    np.int64, 3 * n).reshape(n, 3)
                start = line.timestamp_ns + raw[:, 0] * 1e-3
                uniq, op = np.unique(raw[:, 2], return_inverse=True)
                devices.append(_Ops(start, start + raw[:, 1] * 1e-3, op,
                                    [meta[int(i)].name for i in uniq]))
    return _reduce(host, devices)


def _union(start, end):
    """Sorted disjoint intervals covering the given ones."""
    if not len(start):
        return start, end
    order = np.argsort(start, kind="stable")
    a, b = start[order], np.maximum.accumulate(end[order])
    new = np.ones(len(a), bool)
    new[1:] = a[1:] > b[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(a) - 1)
    return a[first], b[last]


def _busy_until(t, starts, ends):
    """Busy time of the sorted disjoint intervals before each ``t``."""
    if not len(starts):
        return np.zeros_like(t)
    before = np.concatenate([[0.0], np.cumsum(ends - starts)])
    i = np.searchsorted(starts, t, side="right")
    j = np.maximum(i - 1, 0)
    part = np.clip(np.minimum(t, ends[j]) - starts[j], 0.0, None)
    return np.where(i > 0, before[j] + part, 0.0)


def _reduce(host, devices) -> dict | None:
    wins = sorted((a, b) for a, b, name in host if name == WINDOW_SPAN)
    if not wins or not devices:
        return None
    w0, w1 = wins[0]
    busy, op_ns = [], {}
    first = None
    for dev in devices:
        a, b = np.maximum(dev.start, w0), np.minimum(dev.end, w1)
        keep = b > a
        a, b, op = a[keep], b[keep], dev.op[keep]
        per_op = np.bincount(op, weights=b - a, minlength=len(dev.names))
        for i in np.flatnonzero(per_op):
            op_ns[dev.names[i]] = (op_ns.get(dev.names[i], 0.0)
                                  + float(per_op[i]))
        starts, ends = _union(a, b)
        busy.append(float(np.sum(ends - starts)))
        if first is None:
            first = (starts, ends)
    n = len(devices)
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "devices": n,
        "op_s": {k: v / n * 1e-9 for k, v in op_ns.items()},
        "idle_s": {k: v * 1e-9 for k, v in
                   _attribute_gaps(first, host, w0, w1).items()},
    }


def _attribute_gaps(busy, host, w0, w1) -> dict[str, float]:
    """Idle time of ``busy``'s complement in [w0, w1], split by the host
    spans that cover it (spans do not overlap); the rest is
    ``no span``."""
    starts, ends = busy
    spans = [(max(a, w0), min(b, w1), name) for a, b, name in host
             if name != WINDOW_SPAN and min(b, w1) > max(a, w0)]
    out: dict[str, float] = {}
    if spans:
        s0 = np.array([s[0] for s in spans])
        s1 = np.array([s[1] for s in spans])
        idle = (s1 - s0) - (_busy_until(s1, starts, ends)
                            - _busy_until(s0, starts, ends))
        for (_, _, name), v in zip(spans, idle):
            if v > 0:
                out[name] = out.get(name, 0.0) + float(v)
    total = (w1 - w0) - float(np.sum(ends - starts))
    rest = total - sum(out.values())
    if rest > 0:
        out[NO_SPAN] = rest
    return out


def op_seconds(red: dict | None, pattern: str) -> float | None:
    """Device seconds of the operations whose name matches ``pattern``
    (a regular expression), or ``None`` where none ran."""
    if not red:
        return None
    rx = re.compile(pattern)
    hits = [s for name, s in red["op_s"].items() if rx.search(name)]
    return sum(hits) if hits else None


def _short(op: str) -> str:
    """``%name kind`` of an op's HLO text (the trace's op name)."""
    m = re.match(r"(%\S+) = .*?[}\])] ([a-z][\w-]*)\(", op)
    return f"{m.group(1)} {m.group(2)}" if m else op[:120]


def breakdown(red: dict, top: int = 10) -> dict:
    """The ten device operations that took most time and the idle
    seconds by host span, largest first."""
    ops = sorted(((_short(k), v) for k, v in red["op_s"].items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
