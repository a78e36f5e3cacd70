"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
benchmark reads: device busy time, device time per operation and per
program (XLA module), and the longest idle gaps of the device, each named
by the host event that covers most of it.

A device plane is a chip: ``/device:<kind>:<n>`` with no suffix, and not
a CPU.  Its busy time is the union of the intervals of its operation
events: the ``XLA Ops`` line where the plane has one, else every line.
Module times come from its ``XLA Modules`` line.  An operation is named
by its HLO instruction and opcode (``run.1:custom-call``); Pallas kernels
(``tpu_custom_call``) are also summed on their own.  Times are seconds.

    python bench/trace_reduce.py <trace dir or .xplane.pb>   # prints JSON
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Tuple

#: idle gaps shorter than this are not reported (seconds)
MIN_GAP_S = 1e-4
TOP = 10


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


_DEVICE = re.compile(r"^/device:(?!CPU)[A-Za-z_]+:\d+$")
_HLO = re.compile(r"^%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")


def _is_device(name: str) -> bool:
    return bool(_DEVICE.match(name))


def op_name(event_name: str) -> str:
    """``%run.1 = f32[..] custom-call(...)`` -> ``run.1:custom-call``;
    other names as they are."""
    m = _HLO.match(event_name)
    return f"{m.group(1)}:{m.group(2)}" if m else event_name


def reduce_planes(planes, window_s: float = None) -> Dict:
    """``planes``: objects with ``name`` and ``lines``, each line with
    ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them."""
    planes = list(planes)
    devices, host = [], []
    for pl in planes:
        if _is_device(pl.name):
            devices.append(pl)
        elif pl.name.startswith("/host:CPU"):
            host.append(pl)
    op_s: Dict[str, float] = {}
    kernel_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    busy = []
    spans = []                           # (start, end) over every device
    lo, hi = float("inf"), float("-inf")
    for pl in devices:
        lines = list(pl.lines)
        ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
        iv = []
        for ln in ops:
            for e in ln.events:
                a = e.start_ns * 1e-9
                d = e.duration_ns * 1e-9
                iv.append((a, a + d))
                name = op_name(e.name)
                op_s[name] = op_s.get(name, 0.0) + d
                if "tpu_custom_call" in e.name:
                    kernel_s[name] = kernel_s.get(name, 0.0) + d
        for ln in lines:
            if ln.name == "XLA Modules":
                for e in ln.events:
                    module_s[e.name] = module_s.get(e.name, 0.0) \
                        + e.duration_ns * 1e-9
        u = _union(iv)
        busy.append(sum(b - a for a, b in u))
        spans += u
        if u:
            lo, hi = min(lo, u[0][0]), max(hi, u[-1][1])
    n_dev = max(len(devices), 1)
    gaps = []
    merged = _union(spans)
    for (_, a), (b, _) in zip(merged, merged[1:]):
        if b - a >= MIN_GAP_S:
            gaps.append((a, b))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    host_ev = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                e.name) for pl in host for ln in pl.lines
               for e in ln.events if e.duration_ns > 0]
    named = []
    for a, b in gaps:
        best, cover = "host (no traced event)", 0.0
        for s, e, name in host_ev:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        named.append([best, b - a])
    top = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "devices": len(devices),
        "planes": [pl.name for pl in planes],
        "busy_s": sum(busy) / n_dev,
        "window_s": window_s if window_s is not None
        else (hi - lo if devices and hi > lo else 0.0),
        "device_ops": [[k, v] for k, v in top[:TOP]],
        "op_s": dict(top[:200]),
        "kernel_s": kernel_s,
        "module_s": module_s,
        "idle_gaps": named,
    }


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce_dir(path: str, window_s: float = None) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(find_xplane(path)).planes,
                         window_s)


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1]), indent=1))
