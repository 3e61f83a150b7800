"""The collective stitching check of a several-chip cell: for each
capture the probe parsed in the window, the groups the program stitches
from its own spans (deepflow_tpu.tpuprobe.collectives.stitch, what the
server's TpuCollectives answers) against a plain grouping of the same
capture's collective events, read from the XSpace by ProfileData.

The plain grouping: on each device, the train step's launches ("XLA
Modules") and the collective ops inside each ("XLA Ops"). A launch of
device 0 is whole when every device holds a launch that overlaps it; its
groups are, per collective op, the devices that ran the op inside their
overlapping launch. Launches are matched between the two sides by
order: both read the same "XLA Modules" events.

Compared (limit 0): groups of whole launches that the program stitched
with other participants than the plain grouping. Reported, not
compared: groups short of all devices outside whole launches, with
where each lies (its launch's place among the capture's launches).
"""

from __future__ import annotations

import re

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")


def _planes(xspace: bytes, module: str) -> dict:
    """{device: [(start, end, [collective op names inside])]}"""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        m = _DEVICE_RE.match(plane.name)
        if not m:
            continue
        launches, colls = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                launches += [(e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.split("(")[0] == module]
            elif line.name == "XLA Ops":
                colls += [(e.start_ns, re.match(r"%?([^\s=]+)",
                                                e.name).group(1))
                          for e in line.events]
        colls = [(t, op) for t, op in colls if op.startswith(COLLECTIVE)]
        out[int(m.group(1))] = [
            (s, e, sorted(op for t, op in colls if s <= t < e))
            for s, e in sorted(launches)]
    return out


def reference_groups(xspace: bytes, module: str) -> list:
    """Per launch of device 0, in order: None where some device holds no
    overlapping launch, else {op: participating devices}."""
    planes = _planes(xspace, module)
    if 0 not in planes:
        return []
    out = []
    for s0, e0, _ in planes[0]:
        groups: dict = {}
        whole = True
        for dev, launches in planes.items():
            over = [lc for lc in launches if min(e0, lc[1]) > max(s0, lc[0])]
            if not over:
                whole = False
                break
            for op in over[0][2]:
                groups.setdefault(op, set()).add(dev)
        out.append(groups if whole else None)
    return out


def check_capture(xspace: bytes, spans: list, module: str) -> dict:
    from deepflow_tpu.tpuprobe.collectives import stitch
    ref = reference_groups(xspace, module)
    n_dev = len({s.device_id for s in spans if s.hlo_op})
    # device 0's launches of the step, in order, as the program numbers them
    runs = [s.run_id for s in sorted(
        (s for s in spans if not s.hlo_op and s.device_id == 0
         and s.hlo_module == module), key=lambda s: s.start_ns)]
    groups = stitch(spans)
    by_run: dict = {}
    for g in groups:
        by_run.setdefault(g.run_id, {})[g.hlo_op] = {
            int(p.rsplit(":", 1)[-1]) for p in g.participants}
    out = {"launches": len(ref), "whole_launches": 0, "groups_checked": 0,
           "groups_off": 0, "short_groups": 0, "short_where": []}
    if len(runs) != len(ref):
        out["groups_off"] += 1      # the two sides read different launches
        return out
    whole_runs = set()
    for k, (run_id, want) in enumerate(zip(runs, ref)):
        if want is None:
            continue
        whole_runs.add(run_id)
        out["whole_launches"] += 1
        got = by_run.get(run_id, {})
        out["groups_checked"] += len(want)
        out["groups_off"] += sum(got.get(op) != devs
                                 for op, devs in want.items())
        out["groups_off"] += len(set(got) - set(want))
    pos = {r: k for k, r in enumerate(runs)}
    for g in groups:
        if len(g.participants) >= n_dev:
            continue
        out["short_groups"] += 1
        if len(out["short_where"]) < 8:
            k = pos.get(g.run_id)
            out["short_where"].append({
                "run_id": g.run_id, "op": g.hlo_op,
                "devices": sorted(g.participants),
                "launch": (f"{k + 1} of {len(runs)}" if k is not None
                           else "no launch of device 0"),
                "whole_launch": g.run_id in whole_runs})
    out["short_inside_whole"] = sum(
        len(g.participants) < n_dev and g.run_id in whole_runs
        for g in groups)
    return out


def check(captures: list, module: str) -> dict:
    """The stitching check over every capture of the window."""
    total: dict = {"captures": 0, "launches": 0, "whole_launches": 0,
                   "groups_checked": 0, "groups_off": 0, "short_groups": 0,
                   "short_inside_whole": 0, "short_where": []}
    for xspace, spans, _ in captures:
        one = check_capture(xspace, spans, module)
        total["captures"] += 1
        for k in ("launches", "whole_launches", "groups_checked",
                  "groups_off", "short_groups", "short_inside_whole"):
            total[k] += one.get(k, 0)
        total["short_where"] += one["short_where"][:8 - len(
            total["short_where"])]
    return total
