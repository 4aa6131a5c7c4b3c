"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, the
device operations that took the most time, and the longest idle gaps with
what the host was doing in each.

- A device is a plane named ``/device:<kind>:<n>``; its operations are the
  events of its ``XLA Ops`` and ``Async XLA Ops`` lines (copies and other
  asynchronous work in flight count as busy).
- Busy time is the union of those intervals inside the window, so ops that
  overlap count once; ``busy_s`` is the mean over the devices that ran an
  operation.
- The window is the host span named ``WINDOW`` that the benchmark opens
  around its measured window.
- An idle gap is a stretch of the window in which a device runs no
  operation. It is named after the program's innermost annotation
  (``hs:*``, the one that started last) open at its midpoint; where none
  is open, after the benchmark's host span (``bench:*``) that started
  last, or ``none``. A name keeps no metadata (``#query_id=..#``).
"""

from __future__ import annotations

import glob
import os

WINDOW = "bench:window"
SPAN_PREFIX = "bench:"
PROGRAM_PREFIX = "hs:"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
TOP = 10


def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` pairs."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(text: str) -> str:
    """``fusion.12`` of an event named by its HLO text
    (``%fusion.12 = f32[] fusion(...)``); other names as they are."""
    return text.split(" = ", 1)[0].lstrip("%") if " = " in text else text


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def reduce(profile) -> dict | None:
    """``{busy_s, window_s, devices, device_ops, idle_gaps}`` from a
    ``jax.profiler.ProfileData``; None when the trace holds no window or no
    device operation."""
    spans, devices = [], {}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in OPS_LINES:
                    devices.setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns, op_name(e.name))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name.split("#", 1)[0])
                             for e in line.events
                             if e.name.startswith((SPAN_PREFIX,
                                                   PROGRAM_PREFIX)))
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0]
    ops: dict = {}
    busy, gaps = [], []
    for events in devices.values():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events
                   if e > w0 and s < w1]
        if not clipped:
            continue
        for s, e, n in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s) / 1e9
        merged = union((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    if not busy:
        return None
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW]

    def host_at(t: float) -> str:
        for prefix in (PROGRAM_PREFIX, SPAN_PREFIX):
            open_ = [(s, n) for s, e, n in inner
                     if s <= t < e and n.startswith(prefix)]
            if open_:
                return max(open_)[1]
        return "none"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": (w1 - w0) / 1e9,
        "devices": len(busy),
        "device_ops": sorted(([n, s] for n, s in ops.items()),
                             key=lambda x: -x[1])[:TOP],
        "idle_gaps": [[host_at((s + e) / 2), (e - s) / 1e9]
                      for s, e in gaps[:TOP]],
    }


def reduce_dir(log_dir: str) -> dict | None:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(log_dir)))
