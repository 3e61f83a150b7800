"""XSpace (xplane.pb) parser -> TpuSpanEvents.

Parses the tsl profiler's XSpace protobuf with the generic wire reader
(pbwire.py) — schema pinned empirically against real captures on this image
(field numbers match tsl/profiler/protobuf/xplane.proto):

    XSpace        { repeated XPlane planes = 1; ... }
    XPlane        { id=1; name=2; repeated XLine lines=3;
                    map event_metadata=4; map stat_metadata=5; stats=6 }
    XLine         { id=1; name=2; repeated XEvent events=4; timestamp_ns=3 }
    XEvent        { metadata_id=1; offset_ps=2; duration_ps=3; stats=4 }
    XStat         { metadata_id=1; double=2; uint64=3; int64=4; str=5;
                    bytes=6; ref=7 }
    XEventMetadata{ id=1; name=2; display_name=4 }
    XStatMetadata { id=1; name=2 }

Device planes are '/device:TPU:<n>'; the 'XLA Modules' line carries one
event per executable launch (run_id, program id in the name); 'XLA Ops'
carries per-HLO events with device_offset_ps/device_duration_ps — the same
numbers xprof renders.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from deepflow_tpu.tpuprobe import pbwire as w
from deepflow_tpu.tpuprobe.events import TpuSpanEvent, classify, split_program_id

# plane-name layouts across TPU generations:
#   /device:TPU:3                      v5e (1 core/chip; observed here)
#   /device:TPU:3 (core 1)             megacore-style per-core planes
#   /device:TPU:3 Core 1               alternate core spelling
_DEVICE_RE = re.compile(
    r"^/device:TPU:(\d+)(?:\s*(?:\(core\s*(\d+)\)|Core\s*(\d+)))?$",
    re.IGNORECASE)


@dataclass
class XStatView:
    name: str
    value: object


@dataclass
class XEventView:
    name: str           # event metadata display_name or name
    long_name: str
    offset_ps: int
    duration_ps: int
    stats: dict = field(default_factory=dict)


@dataclass
class XLineView:
    name: str
    timestamp_ns: int
    events: list = field(default_factory=list)


@dataclass
class XPlaneView:
    name: str
    lines: list = field(default_factory=list)


def _parse_stat(buf: bytes, stat_names: dict[int, str]) -> tuple[str, object]:
    d = w.fields_dict(buf)
    mid = w.first(d, 1, 0)
    name = stat_names.get(mid, str(mid))
    if 2 in d:
        value = w.f64(d[2][0]) if isinstance(d[2][0], int) else d[2][0]
    elif 3 in d:
        value = d[3][0]
    elif 4 in d:
        value = d[4][0]
    elif 5 in d:
        value = w.as_str(d[5][0])
    elif 6 in d:
        value = d[6][0]
    elif 7 in d:
        # ref_value: interned string -> its stat_metadata name
        value = stat_names.get(d[7][0], str(d[7][0]))
    else:
        value = None
    return name, value


def parse_xspace(data: bytes) -> list[XPlaneView]:
    planes = []
    for f, _, v in w.iter_fields(data):
        if f != 1 or not isinstance(v, bytes):
            continue
        pd = w.fields_dict(v)
        name = w.as_str(w.first(pd, 2))
        # metadata maps (stat names first: event-metadata stats need them)
        stat_names: dict[int, str] = {}
        for entry in pd.get(5, []):
            ed = w.fields_dict(entry)
            md = w.fields_dict(w.first(ed, 2, b""))
            mid = w.first(ed, 1, w.first(md, 1, 0))
            stat_names[mid] = w.as_str(w.first(md, 2))
        # XEventMetadata: display name + static per-op stats (hlo_category,
        # flops, bytes_accessed... live here, not on each XEvent)
        event_meta: dict[int, tuple[str, str, dict]] = {}
        for entry in pd.get(4, []):
            ed = w.fields_dict(entry)
            md = w.fields_dict(w.first(ed, 2, b""))
            mid = w.first(ed, 1, w.first(md, 1, 0))
            long_name = w.as_str(w.first(md, 2))
            display = w.as_str(w.first(md, 4)) or long_name
            static_stats = dict(
                _parse_stat(sbuf, stat_names) for sbuf in md.get(5, []))
            event_meta[mid] = (display, long_name, static_stats)
        plane = XPlaneView(name=name)
        for lbuf in pd.get(3, []):
            ld = w.fields_dict(lbuf)
            line = XLineView(
                name=w.as_str(w.first(ld, 2)),
                timestamp_ns=w.first(ld, 3, 0))
            for ebuf in ld.get(4, []):
                edd = w.fields_dict(ebuf)
                mid = w.first(edd, 1, 0)
                display, long_name, static_stats = event_meta.get(
                    mid, (str(mid), "", {}))
                ev = XEventView(
                    name=display,
                    long_name=long_name,
                    offset_ps=w.first(edd, 2, 0),
                    duration_ps=w.first(edd, 3, 0),
                    stats=dict(static_stats))
                for sbuf in edd.get(4, []):
                    sname, sval = _parse_stat(sbuf, stat_names)
                    ev.stats[sname] = sval
                line.events.append(ev)
            plane.lines.append(line)
        planes.append(plane)
    return planes


def extract_device_spans(planes: list[XPlaneView],
                         capture_start_ns: int = 0,
                         stats: dict | None = None) -> list[TpuSpanEvent]:
    """Per-HLO device spans from all /device:TPU:* planes.

    Timestamps: device events carry ps offsets relative to the capture
    session; we emit capture_start_ns + offset so rows line up with
    wall-clock host telemetry (close enough for flame/time-series use).

    `stats` (optional) counts what would otherwise vanish in silence:
    `unknown_device_planes` (a /device: plane whose name the parser does
    not know) and `planes_without_ops` (a TPU plane with no "XLA Ops"
    line — a libtpu that renamed its lines).
    """
    if stats is None:
        stats = {}
    device_planes = []   # (plane, device_id, core_id, module launches)
    for plane in planes:
        m = _DEVICE_RE.match(plane.name)
        if not m:
            if plane.name.startswith("/device:TPU"):
                stats["unknown_device_planes"] = \
                    stats.get("unknown_device_planes", 0) + 1
            continue
        if not any(line.name == "XLA Ops" for line in plane.lines):
            stats["planes_without_ops"] = \
                stats.get("planes_without_ops", 0) + 1
        # module launches: (start_ps, end_ps, run_id, module, program_id)
        modules = []
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                mod_name, program_id = split_program_id(ev.name)
                run_id = int(ev.stats.get("run_id", 0) or 0)
                modules.append((ev.offset_ps, ev.offset_ps + ev.duration_ps,
                                run_id, mod_name, program_id))
        modules.sort()
        device_planes.append((plane, int(m.group(1)),
                              int(m.group(2) or m.group(3) or 0), modules))
    device_planes.sort(key=lambda p: (p[1], p[2]))
    _align_run_ids([mods for _, _, _, mods in device_planes])

    out: list[TpuSpanEvent] = []
    for plane, device_id, core_id, modules in device_planes:
        def owning_module(off_ps: int):
            for ms, me, rid, name, prog in modules:
                if ms <= off_ps < me:
                    return rid, name, prog
            return 0, "", 0

        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                dur_ps = int(ev.stats.get("device_duration_ps",
                                          ev.duration_ps) or ev.duration_ps)
                off_ps = int(ev.stats.get("device_offset_ps",
                                          ev.offset_ps) or ev.offset_ps)
                category = str(ev.stats.get("hlo_category", ""))
                kind, coll = classify(category, ev.name)
                run_id, mod_name, program_id = owning_module(ev.offset_ps)
                bytes_acc = int(ev.stats.get("bytes_accessed", 0) or 0)
                out.append(TpuSpanEvent(
                    start_ns=capture_start_ns + off_ps // 1000,
                    duration_ns=max(1, (dur_ps + 500) // 1000),
                    device_id=device_id,
                    chip_id=device_id,  # 1 core/chip on v5e; refined by topology
                    core_id=core_id,
                    hlo_module=mod_name,
                    hlo_op=ev.name,
                    hlo_category=category,
                    kind=kind,
                    flops=int(ev.stats.get("model_flops", 0) or 0),
                    bytes_accessed=bytes_acc,
                    program_id=program_id,
                    run_id=run_id,
                    collective=coll,
                    bytes_transferred=bytes_acc if coll else 0,
                    replica_group_size=int(
                        ev.stats.get("replica_group_size",
                                     ev.stats.get("group_size", 0)) or 0),
                ))
        # module-level launch spans (for launch-rate metrics / step spans)
        for ms, me, rid, name, prog in modules:
            out.append(TpuSpanEvent(
                start_ns=capture_start_ns + ms // 1000,
                duration_ns=max(1, (me - ms) // 1000),
                device_id=device_id,
                chip_id=device_id,
                hlo_module=name,
                hlo_op="",
                hlo_category="module",
                kind=_module_kind(),
                program_id=prog,
                run_id=rid,
            ))
    return out


def _align_run_ids(per_plane: list[list[tuple]]) -> None:
    """run_id on a TPU plane counts that device's executions: a device
    that ran more programs (init, input placement on device 0) numbers
    the same SPMD launch differently, and stitching by run_id split each
    collective into groups of 3 and 1 on a 2x2 v5e. Each launch takes
    the run_id of the first plane's launch of the same program that it
    overlaps in time (all devices run an SPMD step together); a launch
    that overlaps none keeps its own. Edits the lists in place."""
    if len(per_plane) < 2:
        return
    ref: dict[int, list[tuple]] = {}
    for launch in per_plane[0]:
        ref.setdefault(launch[4], []).append(launch)
    for modules in per_plane[1:]:
        nxt: dict[int, int] = {}   # program -> first ref launch not past
        for i, (ms, me, rid, name, prog) in enumerate(modules):
            cands = ref.get(prog, ())
            j = nxt.get(prog, 0)
            while j < len(cands) and cands[j][1] <= ms:
                j += 1     # launches are sorted: this one ended before
            nxt[prog] = j
            best, best_ov = None, 0
            for r in cands[j:j + 2]:
                ov = min(me, r[1]) - max(ms, r[0])
                if ov > best_ov:
                    best, best_ov = r, ov
            if best is not None:
                modules[i] = (ms, me, best[2], name, prog)


def _module_kind() -> int:
    from deepflow_tpu.proto import pb
    return pb.DEVICE_COMPUTE


def parse_xplane_file(path: str, capture_start_ns: int = 0,
                      stats: dict | None = None) -> list[TpuSpanEvent]:
    with open(path, "rb") as f:
        data = f.read()
    return extract_device_spans(parse_xspace(data), capture_start_ns, stats)

