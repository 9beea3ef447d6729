"""From the profiler's device trace to busy time, kernel time and gaps.

A traced run records the measured window with ``jax.profiler``. Its
``.xplane.pb`` is reduced to a compact event list (``load``), kept next
to it as ``events.json.gz``, and summarised by ``Trace``:

* each TPU plane gives its ``XLA Modules`` line (one event per program
  run: the device is busy while one runs) and its ``XLA Ops`` line (one
  event per HLO instruction, named by the instruction's text, from
  which the instruction name and its opcode, or for a custom call its
  target, are kept);
* the host plane gives the benchmark's own spans (``SPANS``), on the
  same clock.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import shutil
from pathlib import Path

#: host spans the harness and its drivers record
SPANS = (
    "window", "stats", "rounds", "residual_check", "chunk_prep", "submit",
    "flush", "wait_arrival",
)

_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
#: control-flow ops whose time is the time of the ops inside them
CONTAINERS = ("while", "conditional", "call")


def op_name_kind(text: str) -> tuple[str, str]:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ("fusion.3", "fusion");
    a custom call's kind is its target, e.g. "tpu_custom_call"."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, text
    name = head.lstrip("%")
    target = _TARGET.search(rest)
    if target:
        return name, target.group(1)
    opcode = _OPCODE.search(rest)
    return name, opcode.group(1) if opcode else "other"


def load(xplane_path: str) -> dict:
    """The compact event list of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [
                        [e.start_ns, e.duration_ns, e.name] for e in line.events
                    ]
                elif line.name == "XLA Ops":
                    dev["ops"] = [
                        [e.start_ns, e.duration_ns, *op_name_kind(e.name)]
                        for e in line.events
                    ]
            devices[plane.name.rsplit(":", 1)[1]] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.start_ns, e.duration_ns, e.name]
                    for e in line.events if e.name in SPANS
                )
    return {"devices": devices, "host": sorted(host)}


class Recorder:
    """Profiles the measured window into ``out_dir``."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.out_dir))

    def stop(self) -> "Trace":
        import jax

        jax.profiler.stop_trace()
        paths = glob.glob(
            os.path.join(self.out_dir, "**", "*.xplane.pb"), recursive=True
        )
        events = load(max(paths, key=os.path.getmtime))
        with gzip.open(self.out_dir / "events.json.gz", "wt") as f:
            json.dump(events, f)
        return Trace(events)


def _union(intervals) -> list[tuple[float, float]]:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


class Trace:
    """Busy time, kernel time and idle gaps over the traced window."""

    def __init__(self, events: dict):
        self.devices = events["devices"]
        self.host = events["host"]
        windows = [(s, s + d) for s, d, n in self.host if n == "window"]
        if windows:
            self.lo, self.hi = windows[0]
        else:  # a recording without the harness's window span
            starts = [m[0] for d in self.devices.values() for m in d["modules"]]
            ends = [m[0] + m[1] for d in self.devices.values() for m in d["modules"]]
            self.lo, self.hi = min(starts), max(ends)
        self.busy = {
            k: _union(_clip(
                [(s, s + d) for s, d, _ in dev["modules"]], self.lo, self.hi
            ))
            for k, dev in self.devices.items()
        }

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the chips."""
        total = sum(e - s for iv in self.busy.values() for s, e in iv)
        return total / max(1, len(self.busy)) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops(self):
        """(device, start_ns, dur_ns, name, kind) of every op in the window."""
        for k, dev in self.devices.items():
            for s, d, name, kind in dev["ops"]:
                if self.lo <= s < self.hi:
                    yield k, s, d, name, kind

    def op_seconds(self, match) -> float:
        """Device seconds of the ops ``match(name, kind)`` picks, summed
        over the chips."""
        return sum(d for _, _, d, n, k in self.ops() if match(n, k)) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time, and the idle time of chip
        0 by the host span that was open over each gap."""
        by_op: dict[str, float] = {}
        for _, _, d, name, kind in self.ops():
            if kind not in CONTAINERS:
                by_op[name] = by_op.get(name, 0.0) + d / 1e9
        device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": [[n, s] for n, s in self.idle_by_span()[:top]],
        }

    def idle_by_span(self) -> list[tuple[str, float]]:
        """Idle seconds of the first chip, each gap given to the host
        span (other than the window) that overlaps it most."""
        first = self.busy[min(self.busy, key=int)] if self.busy else []
        gaps, t = [], self.lo
        for s, e in first:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.hi:
            gaps.append((t, self.hi))
        spans = sorted((s, s + d, n) for s, d, n in self.host if n != "window")
        starts = [s for s, _, _ in spans]
        longest = max((e - s for s, e, _ in spans), default=0)
        totals: dict[str, float] = {}
        for gs, ge in gaps:
            best, most = "none", 0.0
            lo = bisect.bisect_left(starts, gs - longest)
            for s, e, n in spans[lo:bisect.bisect_left(starts, ge)]:
                overlap = min(e, ge) - max(s, gs)
                if overlap > most:
                    best, most = n, overlap
            totals[best] = totals.get(best, 0.0) + (ge - gs) / 1e9
        return sorted(totals.items(), key=lambda kv: -kv[1])


def is_kernel(name: str, kind: str) -> bool:
    """A Pallas kernel: an XLA custom call to ``tpu_custom_call``."""
    return kind == "tpu_custom_call"
