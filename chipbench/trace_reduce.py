"""Reduction from a jax profiler trace (``.xplane.pb``) to numbers.

* device busy seconds = the union of the intervals in which an operation
  ran on a device plane (``/device:TPU:n``, line ``XLA Ops``), averaged
  over the device planes that ran anything;
* idle share = 1 - busy / window (the caller knows the window);
* per-op totals under the names the trace prints (top 10);
* idle gaps between consecutive device operations, each labelled by the
  innermost host event (any thread of ``/host:CPU``) that spans the gap's
  midpoint, else ``unattributed``; summed per label (top 10).

``load`` turns ``jax.profiler.ProfileData`` into plain lists, so that
``reduce`` can be checked against a recorded or a synthetic trace with no
profiler at hand. On the CPU platform there is no device plane: the
rehearsal (``cpu_fallback=True``) takes the host-plane events that carry
an ``hlo_op`` stat instead, which exercises the same code and is never a
device number.
"""

from __future__ import annotations

import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
# lines of a device plane that are not single operations
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
           "Framework Name Scope", "Source code")
TOP = 10
NAME_MAX = 160


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str) -> list[dict]:
    """``[{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns,
    is_hlo), ...]}]}]`` from an ``.xplane.pb``."""
    import jax

    planes = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        host = plane.name.startswith(HOST_PREFIX)
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                is_hlo = host and any(k == "hlo_op" for k, _v in e.stats)
                events.append((e.name, float(e.start_ns),
                               float(e.duration_ns), is_hlo))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _islands(start: np.ndarray, end: np.ndarray):
    """(island_start, island_end) of the union of [start, end) intervals."""
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    # an interval opens a new island where it starts after all before end
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    return s[first], np.maximum.reduceat(e, first)


def _device_op_events(planes: list[dict], cpu_fallback: bool) -> list[list]:
    """Per device plane, the events that are single operations."""
    per_plane = []
    for p in planes:
        if not p["name"].startswith(DEVICE_PREFIX):
            continue
        by_name = {ln["name"]: ln["events"] for ln in p["lines"]}
        if OPS_LINE in by_name:
            ev = list(by_name[OPS_LINE])
        else:
            ev = [e for ln in p["lines"] if ln["name"] not in NOT_OPS
                  for e in ln["events"]]
        if ev:
            per_plane.append(ev)
    if not per_plane and cpu_fallback:
        ev = [e for p in planes if p["name"].startswith(HOST_PREFIX)
              for ln in p["lines"] for e in ln["events"] if e[3]]
        if ev:
            per_plane.append(ev)
    return per_plane


def _label_gaps(planes: list[dict], g0: np.ndarray, g1: np.ndarray) -> dict:
    """Seconds of idle gap per label: the innermost host event spanning
    the gap's midpoint (events of one thread nest), else unattributed."""
    mid = (g0 + g1) / 2.0
    best_dur = np.full(len(mid), np.inf)
    label = np.full(len(mid), -1, np.int64)
    names: list[str] = []
    for p in planes:
        if not p["name"].startswith(HOST_PREFIX):
            continue
        for ln in p["lines"]:
            ev = [e for e in ln["events"] if e[2] > 0 and not e[3]]
            if not ev:
                continue
            ev.sort(key=lambda e: e[1])
            s = np.array([e[1] for e in ev])
            e_ = s + np.array([e[2] for e in ev])
            base = len(names)
            names.extend(e[0] for e in ev)
            idx = np.searchsorted(s, mid, side="right") - 1
            for _hop in range(8):  # walk out of nested events that ended
                live = idx >= 0
                hit = live & (e_[np.maximum(idx, 0)] >= mid)
                dur = np.where(hit, e_[np.maximum(idx, 0)]
                               - s[np.maximum(idx, 0)], np.inf)
                better = hit & (dur < best_dur)
                best_dur = np.where(better, dur, best_dur)
                label = np.where(better, base + np.maximum(idx, 0), label)
                idx = np.where(live & ~hit, idx - 1, -1)
                if not (idx >= 0).any():
                    break
    out: dict[str, float] = {}
    secs = (g1 - g0) / 1e9
    for i, lab in enumerate(label):
        name = names[lab] if lab >= 0 else "unattributed"
        out[name] = out.get(name, 0.0) + float(secs[i])
    return out


def reduce(planes: list[dict], window_s: float,
           cpu_fallback: bool = False) -> dict | None:
    """The trace's numbers, or None if no operation ran on a device."""
    per_plane = _device_op_events(planes, cpu_fallback)
    if not per_plane:
        return None
    busy, op_seconds, totals, n_ops = [], 0.0, {}, 0
    gap_labels: dict[str, float] = {}
    longest_gap = 0.0
    for ev in per_plane:
        start = np.array([e[1] for e in ev])
        end = start + np.array([e[2] for e in ev])
        i0, i1 = _islands(start, end)
        busy.append(float((i1 - i0).sum()) / 1e9)
        n_ops += len(ev)
        for name, _s, dur, _h in ev:
            totals[name] = totals.get(name, 0.0) + dur / 1e9
            op_seconds += dur / 1e9
        g0, g1 = i1[:-1], i0[1:]  # idle between consecutive islands
        if len(g0):
            longest_gap = max(longest_gap, float((g1 - g0).max()) / 1e9)
            for k, v in _label_gaps(planes, g0, g1).items():
                gap_labels[k] = gap_labels.get(k, 0.0) + v
    n = len(per_plane)
    busy_s = sum(busy) / n

    def top(d: dict) -> list:
        # names as the trace prints them (whole HLO lines on a TPU), cut
        # for the reader only after the totals are made
        return [[k[:NAME_MAX], v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "op_seconds": op_seconds / n,
        "ops": n_ops,
        "device_planes": n,
        "longest_gap_s": longest_gap,
        "device_ops": top(totals),
        "idle_gaps": top({k: v / n for k, v in gap_labels.items()}),
    }
