"""Checks that chip_smoke.py's job makes on what the chip produced: the
agent's xplane parser held to JAX's own reader, and the collectives the
compiled step holds. Verification only; the agent does not use them."""

from __future__ import annotations

import re

from deepflow_tpu.tpuprobe.xplane import parse_xplane_file

_HLO_COLLECTIVE_RE = re.compile(
    r"%([\w.\-]+) = [^=]*? (?:all-reduce|all-gather|reduce-scatter)"
    r"(?:-start)?\(")


def hlo_collective_ops(hlo_text: str) -> list[str]:
    """Names of the cross-device collectives (all-reduce, all-gather,
    reduce-scatter) in a compiled module's text: what one step's
    stitched groups should number."""
    return _HLO_COLLECTIVE_RE.findall(hlo_text)


def check_against_profile_data(path: str, device_id: int = 0) -> dict:
    """Hold the agent's parser to JAX's own reader (jax.profiler.ProfileData) on
    one trace file: per hlo_op on the /device:TPU:<id> "XLA Ops" line, the
    event counts must be equal and the summed device time must agree
    within 1%, or within the storage quantum: spans are kept in whole
    nanoseconds, at least 1, so each event may move by up to 1 ns (a
    0 ps event becomes 1 ns)."""
    from jax.profiler import ProfileData

    name = f"/device:TPU:{device_id}"
    plane = ProfileData.from_file(path).find_plane_with_name(name)
    if plane is None:
        raise ValueError(f"{path}: no {name} plane")
    ref: dict[str, list] = {}
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            # ProfileData names an op by its HLO text: '%fusion.3 = ...'
            op = re.match(r"%?([^\s=]+)", ev.name).group(1)
            ps = dict(ev.stats).get("device_duration_ps")
            c = ref.setdefault(op, [0, 0.0])
            c[0] += 1
            c[1] += ps / 1000 if ps is not None else ev.duration_ns
    ours: dict[str, list] = {}
    for e in parse_xplane_file(path):
        if e.device_id == device_id and e.hlo_op:
            c = ours.setdefault(e.hlo_op, [0, 0])
            c[0] += 1
            c[1] += e.duration_ns
    mismatched, within_1pct, max_rel = [], 0, 0.0
    for op in sorted(set(ref) | set(ours)):
        n_ref, t_ref = ref.get(op, (0, 0.0))
        n, t = ours.get(op, (0, 0))
        rel = abs(t - t_ref) / t_ref if t_ref else float(t != 0)
        within_1pct += rel <= 0.01
        if n_ref and t_ref / n_ref >= 100:
            max_rel = max(max_rel, rel)  # ops the quantum cannot explain
        if n != n_ref or (rel > 0.01 and abs(t - t_ref) > n):
            mismatched.append({"op": op, "events": n, "ref_events": n_ref,
                               "ns": t, "ref_ns": t_ref})
    return {"ops": len(ref), "events": sum(c[0] for c in ref.values()),
            "device_ns": sum(c[1] for c in ref.values()),
            "ops_within_1pct": within_1pct,
            "max_rel_diff_ops_from_100ns": max_rel,
            "mismatched": mismatched}
