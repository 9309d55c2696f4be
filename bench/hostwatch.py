"""What the host did to this process during the measured window: CPU
time, context switches, page faults, garbage collection, and, where the
machine shows them, CPU time stolen by the hypervisor and the cgroup's
CPU throttling.  Read before and after the window; the differences go on
an earlier line of a run's output, to find the cause of slow pushes."""
from __future__ import annotations

import gc
import os
import resource
import time
from pathlib import Path

_CGROUP_STATS = (Path("/sys/fs/cgroup/cpu.stat"),
                 Path("/sys/fs/cgroup/cpu/cpu.stat"),
                 Path("/sys/fs/cgroup/cpu,cpuacct/cpu.stat"))


class GcClock:
    """Seconds spent in Python's garbage collector while installed."""

    def __init__(self):
        self.seconds, self.runs, self._t = 0.0, 0, 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.runs += 1

    def close(self):
        gc.callbacks.remove(self._cb)


def _steal_ticks():
    try:
        cpu = Path("/proc/stat").read_text().split("\n", 1)[0].split()
        return int(cpu[8]) if len(cpu) > 8 else None
    except OSError:
        return None


def _cgroup():
    for p in _CGROUP_STATS:
        try:
            rows = dict(line.split() for line in p.read_text().splitlines())
        except (OSError, ValueError):
            continue
        return {k: int(v) for k, v in rows.items()
                if k in ("nr_throttled", "throttled_usec", "throttled_time")}
    return {}


def snapshot() -> dict:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    snap = {"user_s": ru.ru_utime, "sys_s": ru.ru_stime,
            "vol_switches": ru.ru_nvcsw, "invol_switches": ru.ru_nivcsw,
            "major_faults": ru.ru_majflt, "minor_faults": ru.ru_minflt,
            "steal_ticks": _steal_ticks()}
    snap.update({f"cgroup_{k}": v for k, v in _cgroup().items()})
    return snap


def delta(before: dict, after: dict) -> dict:
    out = {k: after[k] - before[k] for k in after
           if before.get(k) is not None and after[k] is not None}
    out["loadavg_1m"] = os.getloadavg()[0]
    return out
