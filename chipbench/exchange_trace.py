"""What the readers of the exchange's device metrics take from the traced
run's own ``.xplane.pb``: ``ctx["trace"]`` holds the ten largest
operations and the totals, and these metrics need operations by name and
by when they ran. The trace lies where ``run.py`` put it: ``trace/`` under
the run's own ``chipbench-*`` directory in the temporary directory (the
run deletes it when it ends). ``trace_reduce``'s loader and its choice of
the events that are single device operations are used as they are.
"""

import glob
import os
import tempfile

import trace_reduce

_LOADED: dict = {}


def planes() -> list | None:
    """The traced run's planes (``trace_reduce.load``), or None."""
    dirs = glob.glob(os.path.join(tempfile.gettempdir(), "chipbench-*",
                                  "trace"))
    found = [p for p in map(trace_reduce.find_xplane, dirs) if p]
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    if path not in _LOADED:
        _LOADED.clear()
        _LOADED[path] = trace_reduce.load(path)
    return _LOADED[path]


def device_ops(cpu_fallback: bool) -> list[list]:
    """Per device plane, the (name, start_ns, dur_ns, is_hlo) events that
    are single operations; [] without a trace."""
    loaded = planes()
    return trace_reduce._device_op_events(loaded, cpu_fallback) \
        if loaded else []


def host_intervals(name: str) -> list[tuple[float, float]]:
    """[start_ns, end_ns) of every host event called ``name``."""
    return [(e[1], e[1] + e[2]) for p in planes() or ()
            if p["name"].startswith(trace_reduce.HOST_PREFIX)
            for ln in p["lines"] for e in ln["events"] if e[0] == name]
