"""The host's own counters over a window, read by the parent before and
after it: whether the machine, and not the system under test, held the
processes back.  Files that the machine does not have are left out.

``throttled_s``  seconds the container's CPU quota held its threads
                 (cgroup v2 ``cpu.stat``), ``nr_throttled`` periods
``cpu_s``        CPU seconds the container used in the window
``steal_s``      CPU seconds the hypervisor gave to others, summed over
                 the host's CPUs (``/proc/stat``)
``psi_cpu_s``    seconds in which some runnable thread waited for a CPU
                 (``/proc/pressure/cpu``)
"""
from __future__ import annotations

from pathlib import Path


def _read(path: str) -> str:
    try:
        return Path(path).read_text()
    except OSError:
        return ""


def snapshot() -> dict:
    out = {}
    for line in _read("/sys/fs/cgroup/cpu.stat").splitlines():
        k, _, v = line.partition(" ")
        if k in ("usage_usec", "nr_throttled", "throttled_usec"):
            out[k] = int(v)
    stat = _read("/proc/stat").split("\n", 1)[0].split()
    if len(stat) > 8 and stat[0] == "cpu":
        out["steal_ticks"] = int(stat[8])
    for line in _read("/proc/pressure/cpu").splitlines():
        if line.startswith("some"):
            out["psi_usec"] = int(line.rsplit("total=", 1)[1])
    quota = _read("/sys/fs/cgroup/cpu.max").split()
    if quota:
        out["quota"] = " ".join(quota)
    return out


def delta(before: dict, after: dict) -> dict:
    import os
    tick = os.sysconf("SC_CLK_TCK")
    d = {}
    for k, name, scale in (("throttled_usec", "throttled_s", 1e-6),
                           ("nr_throttled", "nr_throttled", 1),
                           ("usage_usec", "cpu_s", 1e-6),
                           ("steal_ticks", "steal_s", 1.0 / tick),
                           ("psi_usec", "psi_cpu_s", 1e-6)):
        if k in before and k in after:
            d[name] = (after[k] - before[k]) * scale
    if "quota" in after:
        d["cpu_max"] = after["quota"]
    d["cpus"] = os.cpu_count()
    return d
