"""The benchmark's reduction of a profiler trace to device metrics.

Reads an XSpace with jax.profiler.ProfileData (JAX's own reader, not the
program's parser). Per device plane "/device:TPU:<n>":

- busy: the union of the "XLA Ops" intervals. Control-flow parents
  (`while`, `conditional`, `call`) contain their children's intervals, so
  a sum of durations would count a loop body twice; the union cannot.
- window: from the first to the last op of the plane.
- idle gaps: the stretches of the window outside the union, each named
  by the host event that overlaps it most, on the step-driving thread
  (the one whose TraceAnnotations start with "perfbench.") where the
  trace has one.
- self time of each op: its duration minus the ops nested inside it on
  the same line; the top ops are ranked by it.
- exposed collective time: the part of the collective ops' intervals
  during which no leaf (non-container, non-collective) op runs on that
  device, per step (launch on the "XLA Modules" line).
"""

from __future__ import annotations

import re

COLLECTIVE = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute")
HOST_MARK = "perfbench."   # TraceAnnotations of the job's step loop
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
_OP_RE = re.compile(r"%?([^\s=]+)")


def _op_name(name: str) -> str:
    return _OP_RE.match(name).group(1)


def _duration_ns(ev) -> float:
    ps = dict(ev.stats).get("device_duration_ps")
    return ps / 1000.0 if ps is not None else float(ev.duration_ns)


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def _subtract(a: list, b: list) -> float:
    """Length of the union `a` outside the union `b` (both merged)."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while cur < e:
            if k >= len(b) or b[k][0] >= e:
                total += e - cur
                break
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
    return total


def _self_times(events: list) -> list:
    """[(op, self ns)] for (start, end, op) events of one line: each
    event's duration minus the events nested directly inside it."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    self_ns = [e - s for s, e, _ in evs]
    stack: list = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return [(evs[i][2], self_ns[i]) for i in range(len(evs))]


def _leaves(events: list) -> list:
    """The events that contain no other event of the line."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    is_parent = [False] * len(evs)
    stack: list = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            is_parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, is_parent) if not p]


def reduce_profile(pd, n_devices: int | None = None, top: int = 10) -> dict:
    """Device metrics of one trace (a ProfileData)."""
    host_lines: list = []
    devices: dict[int, dict] = {}
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            host_lines += [[(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events]
                           for line in plane.lines]
            continue
        m = _DEVICE_RE.match(plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        if n_devices is not None and dev >= n_devices:
            continue
        ops, steps = [], 0
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    s = float(ev.start_ns)
                    ops.append((s, s + _duration_ns(ev), _op_name(ev.name)))
            elif line.name == "XLA Modules":
                steps += sum(1 for _ in line.events)
        devices[dev] = {"ops": ops, "steps": steps}
    if not devices or not any(d["ops"] for d in devices.values()):
        return {}
    # the thread that drives the steps marks them: its line says what
    # the host was doing in a gap, where idle threads would say "sleep"
    marked = [ln for ln in host_lines
              if any(n.startswith(HOST_MARK) for _, _, n in ln)]
    host = [ev for ln in (marked or host_lines) for ev in ln]
    busy_s, window_s, self_ns, gaps = [], [], {}, []
    coll_ns, exposed_ns, coll_steps = 0.0, 0.0, 0
    for dev, d in sorted(devices.items()):
        ops = d["ops"]
        if not ops:
            continue
        u = union([(s, e) for s, e, _ in ops])
        t0, t1 = u[0][0], u[-1][1]
        busy_s.append(_length(u) / 1e9)
        window_s.append((t1 - t0) / 1e9)
        for op, ns in _self_times(ops):
            self_ns[op] = self_ns.get(op, 0.0) + ns
        if dev == min(devices):
            for (_, a), (b, _) in zip(u, u[1:]):
                gaps.append((a, b))
        colls = [(s, e) for s, e, op in ops if op.startswith(COLLECTIVE)]
        if colls:
            cu = union(colls)
            compute = union([(s, e) for s, e, op in _leaves(ops)
                             if not op.startswith(COLLECTIVE)])
            coll_ns += _length(cu)
            exposed_ns += _subtract(cu, compute)
            coll_steps += max(1, d["steps"])
    n = len(busy_s)
    out = {"busy_s": sum(busy_s) / n, "window_s": sum(window_s) / n,
           "devices": n,
           "steps": sum(d["steps"] for d in devices.values()) / n}
    out["idle_pct"] = 100.0 * (1.0 - out["busy_s"] / out["window_s"])
    out["device_ops"] = [[op, ns / 1e9] for op, ns in sorted(
        self_ns.items(), key=lambda kv: -kv[1])[:top]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out["idle_gaps"] = [[_host_doing(host, a, b), (b - a) / 1e9]
                        for a, b in gaps[:top]]
    if coll_steps:
        out["coll_exposed_ms"] = exposed_ns / coll_steps / 1e6
        out["coll_ms"] = coll_ns / coll_steps / 1e6
    return out


def _host_doing(host: list, a: float, b: float) -> str:
    best, best_ov, best_len = "no host event", 0.0, 0.0
    for s, e, name in host:
        ov = min(b, e) - max(a, s)
        # the innermost event wins a tie: it says most about the gap
        if ov > best_ov or (ov == best_ov > 0 and e - s < best_len):
            best, best_ov, best_len = name, ov, e - s
    return best


def reduce_file(path: str, n_devices: int | None = None) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), n_devices)
