"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

``start``/``stop`` wrap the JAX profiler around the traced waves; the trace
goes to a directory under ``TMPDIR`` and is deleted once read.  The
reduction works on plain event lists so that it can be checked on a small
recorded trace (``bench/tests``):

* the traced window is the span of the harness's ``bench.wave``
  annotations on the host;
* an ``XLA Ops`` event is named by the HLO instruction it ran
  (``%rns_matmul_pallas.50 = s32[...] custom-call(...)`` reads as
  ``rns_matmul_pallas.50``); ``while``, ``conditional`` and ``call``
  events only contain other ops and are left out;
* busy time is the union of the intervals of the remaining events inside
  that window, averaged over the chips used;
* a kernel's time is the sum of the durations of the events that carry its
  name (the name of the jitted kernel entry, with the compiler's ``.N``
  suffix);
* each idle gap is named by the innermost harness span open on the host at
  its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_SUFFIX = re.compile(r"\.\d+$")
_NAME = re.compile(r"%?([^\s=]+)")
CONTAINERS = ("while", "conditional", "call")


def start() -> str:
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(d)
    return d


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def op_name(event: str) -> str:
    """The instruction name of a device op event."""
    return _NAME.match(event).group(1)


def base_name(op: str) -> str:
    """``rns_matmul_pallas.12`` -> ``rns_matmul_pallas``."""
    return _SUFFIX.sub("", op)


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float                 # mean over the chips used
    op_s: dict                    # op name -> seconds, summed over chips
    gaps: list                    # (seconds, host span) of device 0

    def kernel_time(self, name: str) -> float:
        """Seconds of the events of kernel ``name``, summed over chips."""
        return sum(t for op, t in self.op_s.items() if base_name(op) == name)

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:10]
        return {"device_ops": [[n, t] for n, t in ops],
                "idle_gaps": [[n, t] for t, n in gaps]}


def reduce_events(devices: list, host_spans: list) -> DeviceTrace:
    """``devices``: per chip a list of ``(name, start_ns, end_ns)`` device
    ops; ``host_spans``: ``(name, start_ns, end_ns)`` harness annotations
    on the same clock."""
    waves = [(s, e) for n, s, e in host_spans if n == SPAN_PREFIX + "wave"]
    if not waves:
        raise ValueError("trace holds no bench.wave annotation")
    w0, w1 = min(s for s, _ in waves), max(e for _, e in waves)
    busy, op_s = [], {}
    devices = [[(op_name(n), s, e) for n, s, e in events
                if base_name(op_name(n)) not in CONTAINERS]
               for events in devices]
    for events in devices:
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in events
                   if e > w0 and s < w1]
        busy.append(union_length(clipped))
        for n, s, e in events:
            if e > w0 and s < w1:
                op_s[n] = op_s.get(n, 0.0) + (min(e, w1) - max(s, w0)) / 1e9
    gaps, last = [], w0
    inner = sorted(host_spans, key=lambda x: x[2] - x[1])
    for s, e in sorted((max(s, w0), min(e, w1)) for _, s, e in devices[0]
                       if e > w0 and s < w1) + [(w1, w1)]:
        if s > last:
            mid = (s + last) / 2
            name = next((n[len(SPAN_PREFIX):] for n, hs, he in inner
                         if hs <= mid <= he), "host")
            gaps.append(((s - last) / 1e9, name))
        last = max(last, e)
    return DeviceTrace(window_s=(w1 - w0) / 1e9,
                       busy_s=sum(busy) / len(busy) / 1e9,
                       op_s=op_s, gaps=gaps)


def read_events(path: str, n_devices: int):
    """Device ops and harness spans of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) < n_devices and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (op_name(e.name), e.start_ns, e.end_ns)
                    for e in line.events]
            elif plane.name.startswith("/host"):
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    if len(devices) < n_devices:
        raise ValueError(f"trace has ops of {sorted(devices)} only, "
                         f"wanted {n_devices} device(s)")
    return [devices[i] for i in range(n_devices)], host


def reduce(directory: str, devices) -> DeviceTrace:
    """Read and reduce the trace in ``directory``, then delete it."""
    try:
        files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise ValueError(f"no trace was written under {directory}")
        dev, host = read_events(files[0], len(devices))
        return reduce_events(dev, host)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
