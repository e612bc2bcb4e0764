"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
per-operation device time and idle gaps, inside one traced window.

The window is the host event of a ``jax.profiler.TraceAnnotation`` the
benchmark wraps around the traced pass (``anchor``).  Device planes are
``/device:TPU:<n>``; their operations are the events of the ``XLA Ops``
line.  Times are nanoseconds on the profiler's clock, which the host and
device planes share.
"""

from __future__ import annotations

import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(profile_dir: str) -> str:
    paths = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {profile_dir}, found {paths}")
    return paths[0]


def anchor_window(profile, anchor: str) -> tuple[int, int]:
    """``(start_ns, end_ns)`` of the host event named ``anchor``."""
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == anchor:
                    return int(ev.start_ns), int(ev.end_ns)
    raise KeyError(f"no host event {anchor!r} in the trace")


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of ``[n, 2]`` half-open intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2), dtype=np.int64)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.int64)


def op_name(hlo: str) -> str:
    """``%name = type[shape]`` of an ``XLA Ops`` event, whose name is the
    op's whole HLO text."""
    lhs, _, rhs = hlo.partition(" = ")
    return f"{lhs} = {rhs.split(' ', 1)[0]}" if rhs else lhs


def reduce_trace(path: str, anchor: str) -> dict:
    """Busy intervals, per-op time and idle gaps inside the anchor window.

    Returns ``window_ns``, ``devices`` (planes with at least one op in the
    window), ``busy_ns`` (union of op intervals, averaged over those
    devices), ``ops`` (``{name: ns}`` summed over devices, clipped to the
    window) and ``gaps`` (``[n, 2]`` idle intervals of the first device).
    """
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    w0, w1 = anchor_window(profile, anchor)
    ops: dict[str, int] = {}
    busy, gaps, devices = [], None, []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(int(ev.start_ns), w0)
                e = min(int(ev.end_ns), w1)
                if e <= s:
                    continue
                spans.append((s, e))
                name = op_name(ev.name)
                ops[name] = ops.get(name, 0) + (e - s)
        if not spans:
            continue
        devices.append(plane.name)
        u = union(np.asarray(spans, dtype=np.int64))
        busy.append(int((u[:, 1] - u[:, 0]).sum()))
        if gaps is None:
            edges = np.concatenate([[w0], u.ravel(), [w1]])
            g = edges.reshape(-1, 2)
            gaps = g[g[:, 1] > g[:, 0]]
    return {
        "window_ns": (w0, w1),
        "devices": devices,
        "busy_ns": float(np.mean(busy)) if busy else 0.0,
        "ops": ops,
        "gaps": gaps if gaps is not None else np.asarray([[w0, w1]]),
    }


def label_gaps(gaps: np.ndarray, spans: list[dict],
               threads: list[str]) -> dict[str, float]:
    """Idle seconds by what the host threads were doing in each gap: the
    innermost span of each thread open at the gap's midpoint ("-" where
    none is).  ``spans`` carry ``thread``, ``name``, ``start_ns`` and
    ``end_ns`` on the profiler's clock."""
    tables = []
    for t in threads:
        mine = [sp for sp in spans if sp["thread"] == t]
        tables.append((
            np.asarray([sp["start_ns"] for sp in mine], dtype=np.int64),
            np.asarray([sp["end_ns"] for sp in mine], dtype=np.int64),
            [sp["name"] for sp in mine],
        ))
    out: dict[str, float] = {}
    for s, e in gaps:
        mid = (int(s) + int(e)) // 2
        parts = []
        for t, (starts, ends, names) in zip(threads, tables):
            open_ = np.nonzero((starts <= mid) & (ends > mid))[0]
            # innermost: the open span that started last
            name = names[open_[np.argmax(starts[open_])]] if len(open_) else "-"
            parts.append(f"{t}: {name}")
        label = " | ".join(parts)
        out[label] = out.get(label, 0.0) + (int(e) - int(s)) / 1e9
    return out
