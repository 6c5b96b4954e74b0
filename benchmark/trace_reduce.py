"""Reduce a JAX profiler trace (`.xplane.pb`) of the measured window to
the numbers the per-layer readers and the breakdown take.

- busy: the union of the intervals in which an operation ran on a
  device (its "XLA Ops" line), averaged over the devices;
- modules: total device time and count of each program on the
  devices' "XLA Modules" line, by program name (the jit name, without
  the trailing "(id)");
- device_ops: the device operations that took most time, each named
  by its program and the HLO name of the op ("jit_f/%fusion.3", the
  text before " = " of the event's name);
- ops: every device operation's interval and name, per device, for a
  reader that sums a kind of operation (`op_seconds`);
- idle_gaps: the longest gaps between device operations, each named by
  the host event that spans it (its thread, then its name).
"""

from __future__ import annotations

import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_ID = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    return _ID.sub("", event_name)


def _op_names(ops, modules) -> list:
    """`program/op` of each (start, name) in `ops`: the program is the
    module event that holds the op's start, if one does."""
    mods = sorted(modules)
    starts = np.asarray([m[0] for m in mods], np.int64)
    out = []
    for s, name in ops:
        short = name.split(" = ", 1)[0]
        k = int(np.searchsorted(starts, s, side="right")) - 1
        if k >= 0 and s < mods[k][1]:
            short = f"{mods[k][2]}/{short}"
        out.append(short)
    return out


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals of (starts, ends)."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(run_end[idx[1:] - 1], run_end[-1])


def load(path: str):
    """A profile from an `.xplane.pb`, or a gzipped one (`.gz`)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def reduce(profile, top: int = 10) -> dict:
    """The reduction of one trace; `busy_s` is None where no device
    operation is in it (a CPU run)."""
    devices, hosts = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            devices.append(plane)
        elif plane.name.startswith("/host:CPU"):
            hosts.append(plane)
    busy, op_time, modules = [], {}, {}
    gaps, op_spans = [], []
    for plane in devices:
        starts, ends, ops, mods = [], [], [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s = int(ev.start_ns)
                    d = int(ev.duration_ns)
                    starts.append(s)
                    ends.append(s + d)
                    ops.append((s, ev.name))
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    name = program_name(ev.name)
                    t, n = modules.get(name, (0, 0))
                    modules[name] = (t + int(ev.duration_ns), n + 1)
                    s = int(ev.start_ns)
                    mods.append((s, s + int(ev.duration_ns), name))
        if not starts:
            continue
        names = _op_names(ops, mods)
        for (s, _), e, name in zip(ops, ends, names):
            op_time[name] = op_time.get(name, 0) + e - s
        op_spans.append((names, np.asarray(starts, np.int64),
                         np.asarray(ends, np.int64)))
        ms, me = _union(np.asarray(starts, np.int64),
                        np.asarray(ends, np.int64))
        busy.append(int((me - ms).sum()))
        if len(ms) > 1:
            g0, g1 = me[:-1], ms[1:]
            for k in np.argsort(g0 - g1, kind="stable")[:top]:
                gaps.append((int(g0[k]), int(g1[k])))
    n_dev = max(len(busy), 1)
    out = {
        "devices": len(busy),
        "busy_s": (sum(busy) / n_dev / 1e9) if busy else None,
        "modules": {
            k: {"seconds": t / n_dev / 1e9, "count": c}
            for k, (t, c) in modules.items()
        },
        "device_ops": [
            [name, t / n_dev / 1e9]
            for name, t in sorted(op_time.items(), key=lambda kv: -kv[1])[
                :top
            ]
        ],
        "ops": op_spans,
    }
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:top]
    host = _host_events(hosts) if gaps else None
    out["idle_gaps"] = [
        [_host_activity(host, g0, g1), (g1 - g0) / 1e9] for g0, g1 in gaps
    ]
    return out


def _host_events(hosts):
    """(starts, ends, label ids, labels) of every host event."""
    labels, index = [], {}
    starts, ends, ids = [], [], []
    for plane in hosts:
        for line in plane.lines:
            for ev in line.events:
                label = f"{line.name}: {ev.name}"
                k = index.get(label)
                if k is None:
                    k = index[label] = len(labels)
                    labels.append(label)
                s = int(ev.start_ns)
                starts.append(s)
                ends.append(s + int(ev.duration_ns))
                ids.append(k)
    return (np.asarray(starts, np.int64), np.asarray(ends, np.int64),
            np.asarray(ids, np.int64), labels)


def _host_activity(host, g0: int, g1: int) -> str:
    """What the host was doing in [g0, g1]: the shortest host event that
    covers at least half of it -- the most specific, not an enclosing
    span -- or else the one that overlaps it most."""
    starts, ends, ids, labels = host
    ov = np.minimum(ends, g1) - np.maximum(starts, g0)
    hit = np.flatnonzero(2 * ov >= (g1 - g0))
    if len(hit):
        best = hit[np.argmin(ends[hit] - starts[hit])]
    else:
        hit = np.flatnonzero(ov > 0)
        if not len(hit):
            return "no host event"
        best = hit[np.argmax(ov[hit])]
    return labels[int(ids[best])]


def module_seconds(reduced: dict, patterns) -> tuple:
    """(device seconds, launches) of the programs whose name matches any
    of the regular expressions `patterns`; None where none ran."""
    rx = [re.compile(p) for p in patterns]
    t = n = 0
    for name, v in reduced.get("modules", {}).items():
        if any(r.search(name) for r in rx):
            t += v["seconds"]
            n += v["count"]
    return (t, n) if n else None


def op_seconds(reduced: dict, patterns):
    """Device seconds, averaged over the devices, in which an operation
    whose `program/op` name matches any of the regular expressions
    `patterns` ran: the union of their intervals, so operations that
    overlap count once.  None where none matched."""
    rx = [re.compile(p) for p in patterns]
    total = found = 0
    for names, starts, ends in reduced.get("ops", []):
        hit = np.fromiter((any(r.search(n) for r in rx) for n in names),
                          bool, len(names))
        found += int(hit.sum())
        ms, me = _union(starts[hit], ends[hit])
        total += int((me - ms).sum())
    if not found:
        return None
    return total / max(len(reduced["ops"]), 1) / 1e9
