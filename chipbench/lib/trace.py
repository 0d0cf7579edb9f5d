"""Read the profiler's trace into the compact form the metric readers take.

The compact trace is plain JSON (so a small one can be kept as test data):

* ``devices``: per device in use, its operations as ``[name, start_ns,
  duration_ns]``, from the device plane's ``XLA Ops`` line, named by their
  HLO instruction;
* ``host``: the harness's own spans (``call``, ``dispatch``, ``wait``)
  on the same clock;
* ``window_ns``: the traced window, from the first call's dispatch to the
  last call's end;
* ``calls``: calls made in the window; ``chips``; ``essential`` (bytes and
  flops of one call, over all chips); ``peaks`` (of one chip);
* ``ops``: the kernels (with operand and result bytes) and collectives of
  the compiled program, by instruction name (see :mod:`chipbench.lib.hlo`);
* ``labels``: each device operation's opcode (and custom-call target).
"""

from __future__ import annotations

import glob
import os
import re

#: The spans :mod:`chipbench.lib.harness` writes around each timed call.
HOST_SPANS = ("call", "dispatch", "wait")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
#: A device operation's event is named by its HLO instruction's text:
#: ``%name = shape opcode(operands), custom_call_target="...", ...``.
_OP = re.compile(r"^%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(text: str) -> tuple:
    """``(instruction name, label)`` of a device event's name; the label is
    the opcode, with the custom-call target where there is one."""
    m = _OP.match(text)
    if m is None:
        return text, text
    target = _TARGET.search(text)
    label = m.group(2) + (f":{target.group(1)}" if target else "")
    return m.group(1), label


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _device_id(plane_name: str):
    tail = plane_name[len(DEVICE_PLANE):]
    return int(tail) if tail.isdigit() else None


def read(xplane_path: str, device_ids) -> dict:
    """``{"devices": [...], "host": [...], "labels": {...}}`` from an
    ``.xplane.pb`` file, devices in the order of ``device_ids``; device
    events are named by their HLO instruction's name, and ``labels`` gives
    each name's opcode."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    by_id: dict = {}
    host: list = []
    labels: dict = {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = _device_id(plane.name)
            if dev is None or dev not in device_ids:
                continue
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = by_id[dev] = []
                for e in line.events:
                    name, label = op_name(e.name)
                    labels[name] = label
                    evs.append([name, e.start_ns, e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append([e.name, e.start_ns, e.duration_ns])
    host.sort(key=lambda e: e[1])
    return {"devices": [by_id.get(d, []) for d in device_ids], "host": host, "labels": labels}


def trim(tr: dict, calls: int) -> dict:
    """The compact trace cut to its first ``calls`` calls: the window ends
    with the last of them, and only the events that start inside it stay."""
    spans = [e for e in tr["host"] if e[0] == "call"][:calls]
    lo = spans[0][1]
    hi = max(s + d for _, s, d in spans)
    inside = lambda evs: [e for e in evs if lo <= e[1] < hi]  # noqa: E731
    return {
        **tr,
        "window_ns": [lo, hi],
        "calls": len(spans),
        "devices": [inside(ev) for ev in tr["devices"]],
        "host": inside(tr["host"]),
    }
