"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced session. On a TPU
each chip is a plane named ``/device:TPU:<n>``, with a line ``XLA Modules``
(one event per program execution, named after the program) and a line
``XLA Ops`` (one event per operation). Host threads are lines of the
``/host:CPU`` plane. Where no device plane holds those lines (the CPU
backend runs its programs on host threads), operations are the events that
carry an ``hlo_module`` stat, and a program's time is the span of its
operations.

All times come back in seconds on the trace's own clock, and the window
is the benchmark's ``bench.window`` annotation.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import warnings

WINDOW_SPAN = "bench.window"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]                  # seconds, trace clock
    # program executions per device: (program name, start s, duration s)
    programs: dict[str, list[tuple[str, float, float]]]
    busy: dict[str, list[tuple[float, float]]]   # merged op intervals
    host: list[tuple[str, float, float]]         # host spans (name, t, d)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.busy:
            return 0.0
        return sum(sum(e - s for s, e in iv)
                   for iv in self.busy.values()) / len(self.busy)

    def program_time(self) -> dict[str, tuple[float, int]]:
        """Device seconds and executions of each program, summed over the
        devices, inside the window."""
        out: dict[str, list] = collections.defaultdict(lambda: [0.0, 0])
        for runs in self.programs.values():
            for name, _, d in runs:
                out[name][0] += d
                out[name][1] += 1
        return {k: (v[0], v[1]) for k, v in out.items()}

    def idle_gaps(self, top: int = 10) -> list[tuple[str, float]]:
        """The longest stretches with no operation on a device, each named
        after the host span that overlapped it most."""
        gaps = []
        w0, w1 = self.window
        for iv in self.busy.values():
            t = w0
            for s, e in iv + [(w1, w1)]:
                if s > t:
                    gaps.append((t, s))
                t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            best, label = 0.0, "no host span"
            for name, hs, hd in self.host:
                ov = min(e, hs + hd) - max(s, hs)
                if ov > best and name != WINDOW_SPAN:
                    best, label = ov, name
            out.append((label, e - s))
        return out


def _merge(intervals):
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(items, w0, w1):
    for name, s, d in items:
        lo, hi = max(s, w0), min(s + d, w1)
        if hi > lo:
            yield name, lo, hi - lo


def program_name(raw: str) -> str:
    """``jit_decode_step(12)`` -> ``jit_decode_step``."""
    return _ID_SUFFIX.sub("", raw.strip())


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def reduce(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    host: list[tuple[str, float, float]] = []
    modules: dict[str, list] = {}
    ops: dict[str, list] = {}
    planes = list(data.planes)
    on_device = any(p.name.startswith("/device:")
                    and any(l.name == "XLA Modules" for l in p.lines)
                    for p in planes)
    with warnings.catch_warnings():
        # reading event stats warns about the binding's own type
        warnings.simplefilter("ignore", DeprecationWarning)
        host_ops = _read(planes, on_device, modules, ops, host)
    window = next((w for w in host if w[0] == WINDOW_SPAN), None)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = window[1], window[1] + window[2]
    window = (w0, w1)
    if not modules:
        # no device plane: programs ran on host threads; a program's
        # execution is the span of its operations on one device
        for dev, evs in host_ops.items():
            ops[dev] = evs
            modules[dev] = [(program_name(n), s, d) for n, s, d in evs]
    programs = {dev: list(_clip(evs, w0, w1)) for dev, evs in modules.items()}
    busy = {dev: _merge((s, s + d) for _, s, d in _clip(evs, w0, w1))
            for dev, evs in ops.items()}
    for dev in programs:
        busy.setdefault(dev, _merge((s, s + d) for _, s, d in programs[dev]))
    return Trace(window=window, programs=programs, busy=busy, host=host)


def _read(planes, on_device, modules, ops, host):
    host_ops: dict[str, list] = collections.defaultdict(list)
    for plane in planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                s, d = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if is_device and line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).append(
                        (program_name(ev.name), s, d))
                elif is_device and line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).append((ev.name, s, d))
                elif not is_device:
                    stats = {} if on_device or d <= 0 else dict(ev.stats)
                    if "hlo_module" in stats:
                        host_ops[str(stats.get("device_ordinal", 0))].append(
                            (str(stats["hlo_module"]), s, d))
                    elif d > 0:
                        host.append((ev.name, s, d))
    return host_ops
