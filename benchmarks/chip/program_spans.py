"""The program's own spans of the FL round, read from a traced run.

The runtime wraps each round of its scheduler in a host span
``fed.round`` and each stage of the round in a span of the stage's
name (``fed.sample_cohort``, ``fed.compute_cohort``, ...), with
``fed.device_wait`` wherever the host blocks on a device result.  From
the ``.xplane.pb`` of a traced run this module takes the host planes'
``fed.*`` events and the benchmark's ``window`` span, keeps the
window's whole rounds (the ``fed.round`` spans that start and end
inside it) and gives, for each stage, the time in its spans nested in
those rounds, per round.  Round 0, in which the engine job waits for
the window to open, and the job's ``finalize`` fall outside.

The file is parsed once: every metric of a run reads the same one.
Where the benchmark's own reduction found no TPU plane
(``ctx.trace`` is ``None``), or the trace has no window or no whole
round, there is nothing to read and each reader gives ``None``; so
does a program without these spans.
"""
from __future__ import annotations

import functools
import os

import numpy as np

from trace_reduce import WINDOW_SPAN, _xplane_pb2, find_xplane

__all__ = ["PREFIX", "ROUND_SPAN", "reduce_spans", "read",
           "stage_ms", "round_p95_ms"]

PREFIX = "fed."
ROUND_SPAN = "fed.round"
# Where run_cell.py has the profiler write the window's trace.
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".trace")


def _wanted(name: str) -> bool:
    return name == WINDOW_SPAN or name.startswith(PREFIX)


def reduce_spans(planes) -> dict | None:
    """``planes`` as :func:`trace_reduce.reduce_trace` takes them.

    → ``rounds`` (whole rounds in the window), ``window_ms``,
    ``round_ms`` (each whole round's duration) and ``stage_ms`` (span
    name → milliseconds per round in spans nested in those rounds), or
    ``None``.
    """
    return _reduce([(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for plane in planes if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if _wanted(ev.name)])


@functools.lru_cache(maxsize=1)
def _read_file(path: str, mtime_ns: int) -> dict | None:
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    events = []
    for plane in space.planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {i: m.name for i, m in plane.event_metadata.items()
                 if _wanted(m.name)}
        for line in plane.lines:
            base = line.timestamp_ns
            for ev in line.events:
                name = names.get(ev.metadata_id)
                if name is not None:
                    a = base + ev.offset_ps * 1e-3
                    events.append((a, a + ev.duration_ps * 1e-3, name))
    return _reduce(events)


def _reduce(events) -> dict | None:
    wins = sorted((a, b) for a, b, name in events if name == WINDOW_SPAN)
    if not wins:
        return None
    w0, w1 = wins[0]
    rounds = np.array(sorted((a, b) for a, b, name in events
                             if name == ROUND_SPAN and a >= w0 and b <= w1),
                      np.float64).reshape(-1, 2)
    n = len(rounds)
    if not n:
        return None
    r0, r1 = rounds[:, 0], rounds[:, 1]
    by_name: dict[str, list] = {}
    for a, b, name in events:
        if name not in (WINDOW_SPAN, ROUND_SPAN):
            by_name.setdefault(name, []).append((a, b))
    stage_ms = {}
    for name, spans in by_name.items():
        a, b = np.asarray(spans, np.float64).T
        i = np.searchsorted(r0, a, side="right") - 1
        inside = (i >= 0) & (b <= r1[np.maximum(i, 0)])
        if inside.any():
            stage_ms[name] = float(np.sum((b - a)[inside])) / n * 1e-6
    return {"rounds": n, "window_ms": (w1 - w0) * 1e-6,
            "round_ms": (r1 - r0) * 1e-6, "stage_ms": stage_ms}


def read(ctx) -> dict | None:
    """The spans of the run ``ctx`` traced, reduced, or ``None``."""
    if ctx.trace is None:
        return None
    path = find_xplane(TRACE_DIR)
    if path is None:
        return None
    return _read_file(path, os.stat(path).st_mtime_ns)


def stage_ms(ctx, name: str) -> float | None:
    """Milliseconds per whole round of the window in ``name`` spans."""
    spans = read(ctx)
    return spans["stage_ms"].get(name) if spans else None


def round_p95_ms(ctx) -> float | None:
    """95th percentile of the window's whole rounds, in milliseconds."""
    spans = read(ctx)
    return float(np.percentile(spans["round_ms"], 95)) if spans else None
