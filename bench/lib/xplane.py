"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle
share, the device operations that took most time and the longest idle
gaps, each gap named by what the host was doing in it.

Device planes are those named ``/device:<kind>:<n>`` other than the
CPU; their operations are the events of the ``XLA Ops`` line. Busy time
is the length of the union of those intervals inside the window; the
window is the host span named ``window_name`` (the benchmark's own
annotation around its measured window), or the whole trace without
one. Host spans whose names start with ``bench.`` are the benchmark's
own; a gap is named by the one of them that overlaps it most, and by
the program's host event that overlaps it most. Operations are summed
under their whole trace name (the HLO text) and reported up to their
layout.

Device and host timestamps come from different clocks; on a v5e the
device's ran 1-2 ms behind the host's (``tests/data/tpu_small.xplane.pb``).
Over a window of seconds that moves the busy share by a few parts in
ten thousand; gaps of a few milliseconds are named only roughly.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

import numpy as np

OPS_LINE = "XLA Ops"
BENCH_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    busy_s: float                      # mean over device planes
    window_s: float
    n_devices: int
    top_ops: list                      # [[name, seconds], ...]
    idle_gaps: list                    # [[label, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union_length(intervals) -> tuple[float, list]:
    """(total length, merged intervals) of ``(start, end)`` pairs."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


DEVICE_RE = re.compile(r"^/device:(?!CPU:)[A-Z_]+:\d+$")


def _is_device(name: str) -> bool:
    """A chip's plane (``/device:TPU:0``), not the CPU's and not a
    trace of its own such as ``/device:CUSTOM:Megascale Trace``."""
    return bool(DEVICE_RE.match(name))


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(profile, window_name: str = "bench.window", top: int = 10,
           min_gap_ns: float = 0.0) -> TraceSummary:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    device_ops = []                    # per plane: [(start, end, name)]
    host = []                          # (start, end, name)
    window = None
    for plane in profile.planes:
        if _is_device(plane.name):
            ops = [(e.start_ns, e.end_ns, e.name)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_name:
                        window = (e.start_ns, e.end_ns)
                    else:
                        host.append((e.start_ns, e.end_ns, e.name))
    if not device_ops:
        raise ValueError("trace has no device plane")
    if window is None:
        starts = [o[0] for ops in device_ops for o in ops]
        ends = [o[1] for ops in device_ops for o in ops]
        window = (min(starts), max(ends))
    w0, w1 = window
    busy = []
    per_op = defaultdict(float)
    gaps = []
    for ops in device_ops:
        clipped = [(max(a, w0), min(b, w1)) for a, b, _ in ops
                   if b > w0 and a < w1]
        total, merged = union_length(clipped)
        busy.append(total)
        for a, b, name in ops:
            per_op[name] += _overlap(a, b, w0, w1)
        edges = [w0] + [x for m in merged for x in m] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 > min_gap_ns:
                gaps.append((g1 - g0, g0, g1))
    gaps.sort(reverse=True)
    host = (np.array([h[0] for h in host], np.float64),
            np.array([h[1] for h in host], np.float64),
            [h[2] for h in host],
            np.array([h[2].startswith(BENCH_PREFIX) for h in host], bool))
    labeled = [[_gap_label(host, g0, g1), length * 1e-9]
               for length, g0, g1 in gaps[:top]]
    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        busy_s=sum(busy) / len(busy) * 1e-9, window_s=(w1 - w0) * 1e-9,
        n_devices=len(device_ops),
        top_ops=[[short_name(n), s * 1e-9] for n, s in ops_sorted],
        idle_gaps=labeled)


def short_name(op: str) -> str:
    """An op's trace name up to its layout: ``%fusion.16 =
    f32[121405440]`` of the whole HLO text the TPU trace gives."""
    return op.split("{", 1)[0].strip()


def _gap_label(host, g0, g1) -> str:
    """'<bench span> / <program host event>' overlapping the gap most
    (the shorter, inner span wins a tie)."""
    starts, ends, names, mine = host
    ov = np.minimum(ends, g1) - np.maximum(starts, g0)
    parts = []
    for sel, none in ((mine, "no bench span"), (~mine, "no host event")):
        cand = np.flatnonzero(sel & (ov > 0))
        if cand.size == 0:
            parts.append(none)
            continue
        key = np.lexsort((ends[cand] - starts[cand], -ov[cand]))
        parts.append(names[cand[key[0]]])
    return " / ".join(parts)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)
