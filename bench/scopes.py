"""Device time by DC-ELM phase, from the profile of a traced run.

The program names its phases on the device (``repro.core.scopes``):
every HLO instruction traced inside one carries ``dcelm/<phase>`` in its
``op_name`` metadata. The TPU profiler keeps it as the ``tf_op`` stat of
each instruction's event metadata in the ``.xplane.pb`` (not among the
stats of the op events, which ``jax.profiler.ProfileData`` lists), so
``op_names`` reads the event metadata from the file's protobuf
encoding. ``load`` reduces the newest ``.xplane.pb`` that
``trace.Recorder`` left in a cell's trace directory to the device ops,
each with its ``op_name``; ``Phases`` sums their device seconds inside
the harness's window by phase:

* an op belongs to the innermost ``dcelm/`` component of its
  ``op_name`` (the feature map called inside the stats pass is
  ``features``);
* control-flow containers (``trace.CONTAINERS``) are left out, as
  ``Trace.breakdown`` leaves them out: their time is their ops' time;
* an op that straddles an end of the window counts for its part inside;
* busy op time in no phase is ``unscoped`` (in the benchmark's cells,
  the residual checks that ``bench/`` adds to each job or chunk).

A program without the scopes has no phase, and ``for_cell`` returns
None: its readers then report nothing.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from pathlib import Path

from bench.harness import OUT
from bench.trace import CONTAINERS, op_name_kind

#: the program's phase names (``repro.core.scopes.PHASES``), kept here
#: because the benchmark also runs programs that have no such module
PHASES = ("features", "stats", "omega", "reseed", "woodbury", "rounds")
_PHASE = re.compile(r"dcelm/(" + "|".join(PHASES) + r")(?![A-Za-z0-9_])")
#: the event metadata's stat that holds the instruction's ``op_name``
OP_NAME_STAT = "tf_op"
#: where traced runs leave their profiles, a directory per cell
TRACES = OUT / "trace"


def phase_of(op_name: str) -> str | None:
    """The innermost ``dcelm/<phase>`` of an ``op_name``, or None."""
    found = _PHASE.findall(op_name)
    return found[-1] if found else None


# Field numbers of tsl/profiler/protobuf/xplane.proto that ``op_names``
# reads: XSpace.planes; XPlane.name, .event_metadata, .stat_metadata
# (maps: key 1, value 2); XEventMetadata.name, .stats; XStat.metadata_id,
# .str_value, .ref_value; XStatMetadata.name.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_META_NAME, _META_STATS = 2, 5
_STAT_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_META_ID, _STAT_META_NAME = 1, 2


def _varint(buf: bytes, pos: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf: bytes, start: int = 0, end: int | None = None):
    """(field number, value) of one protobuf message in ``buf[start:end]``;
    a length-delimited value is its (start, end) in ``buf``."""
    pos, end = start, len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, pos = buf[pos:pos + size], pos + size
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span):
    """The value's (start, end) of one map<int64, message> entry."""
    for field, value in _fields(buf, *span):
        if field == 2:
            return value
    return (span[1], span[1])


def op_names(xplane: bytes) -> dict:
    """{plane name: {event name: op_name}}: the ``tf_op`` stat of every
    event metadata of every plane."""
    planes = {}
    for field, plane in _fields(xplane):
        if field != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for f, value in _fields(xplane, *plane):
            if f == _PLANE_NAME:
                name = _text(xplane, value)
            elif f == _PLANE_EVENT_METADATA:
                events.append(_map_value(xplane, value))
            elif f == _PLANE_STAT_METADATA:
                meta = dict(_fields(xplane, *_map_value(xplane, value)))
                if _STAT_META_NAME in meta:
                    stat_names[meta.get(_STAT_META_ID, 0)] = _text(
                        xplane, meta[_STAT_META_NAME]
                    )
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        found = {}
        for span in events:
            event_name, op_name = "", ""
            for f, value in _fields(xplane, *span):
                if f == _META_NAME:
                    event_name = _text(xplane, value)
                elif f == _META_STATS:
                    stat = dict(_fields(xplane, *value))
                    if stat.get(_STAT_ID) not in wanted:
                        continue
                    if _STAT_STR in stat:
                        op_name = _text(xplane, stat[_STAT_STR])
                    elif _STAT_REF in stat:
                        op_name = stat_names.get(stat[_STAT_REF], "")
            if op_name:
                found[event_name] = op_name
        planes[name] = found
    return planes


def load(xplane_path: str) -> dict:
    """Device ops of one ``.xplane.pb``: for each TPU, a list of
    ``[start_ns, dur_ns, name, kind, op_name]`` (op_name "" where the
    profile holds none)."""
    from jax.profiler import ProfileData

    with open(xplane_path, "rb") as f:
        raw = f.read()
    names = op_names(raw)
    data = ProfileData.from_serialized_xspace(raw)
    devices = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        of = names.get(plane.name, {})
        ops = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [
                    [e.start_ns, e.duration_ns, *op_name_kind(e.name), of.get(e.name, "")]
                    for e in line.events
                ]
        devices[plane.name.rsplit(":", 1)[1]] = ops
    return {"devices": devices}


class Phases:
    """Device seconds by phase inside [lo, hi) (profiler nanoseconds),
    summed over the chips."""

    def __init__(self, events: dict, lo: float, hi: float):
        ns = dict.fromkeys(PHASES, 0.0)
        unscoped = 0.0
        for ops in events["devices"].values():
            for start, dur, _name, kind, op_name in ops:
                if kind in CONTAINERS:
                    continue
                inside = min(start + dur, hi) - max(start, lo)
                if inside <= 0:
                    continue
                phase = phase_of(op_name)
                if phase is None:
                    unscoped += inside
                else:
                    ns[phase] += inside
        self.seconds = {p: t / 1e9 for p, t in ns.items()}
        self.unscoped_s = unscoped / 1e9

    def __getitem__(self, phase: str) -> float:
        return self.seconds[phase]

    @property
    def scoped_s(self) -> float:
        return sum(self.seconds.values())

    @property
    def busy_s(self) -> float:
        """Device seconds of every op that is not a container."""
        return self.scoped_s + self.unscoped_s


@functools.lru_cache(maxsize=1)
def _load_once(path: str, mtime: float) -> dict:
    return load(path)


def events_for(cell_dir: Path) -> dict | None:
    """``load`` of the newest ``.xplane.pb`` under ``cell_dir``, once a
    file (the readers of one run share it); None when there is none."""
    paths = glob.glob(os.path.join(cell_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    newest = max(paths, key=os.path.getmtime)
    return _load_once(newest, os.path.getmtime(newest))


def for_cell(ctx) -> Phases | None:
    """The phases of a traced run's window, or None when its profile
    holds no op of any phase (a program without the scopes)."""
    events = events_for(TRACES / ctx.cell)
    if events is None:
        return None
    phases = Phases(events, ctx.trace.lo, ctx.trace.hi)
    return phases if phases.scoped_s > 0 else None
