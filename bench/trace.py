"""Reduce a JAX profiler trace to what the per-layer metrics read.

The trace is the `.xplane.pb` that `jax.profiler` writes.  On a TPU each
chip is a plane `/device:TPU:<n>`; its line `XLA Ops` holds one event per
HLO operation that ran, named by the HLO instruction (`%fusion.31 = ...`;
a Pallas kernel is a `custom-call` named after its kernel function, such
as `%per_example_sqnorm_multi.1`, with `custom_call_target=
"tpu_custom_call"`).  The host's threads are the lines of `/host:CPU`.
Both are on one clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
TOP = 10


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return files[0]


def load(path: str) -> dict:
    """`{"devices": {plane: [(op, start_ns, dur_ns, is_kernel)]},
    "host": [(thread, name, start_ns, dur_ns)]}` from a trace directory or
    an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = xplane_file(path)
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    head = e.name.split(" = ", 1)[0].lstrip("%")
                    ops.append((head, e.start_ns, e.duration_ns,
                                KERNEL_TARGET in e.name))
            devices[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((line.name, e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    return {"devices": dict(sorted(devices.items())), "host": host}


def busy_ns(ops: list) -> float:
    """Length of the union of the ops' intervals."""
    total, end = 0.0, None
    for _, start, dur, _ in ops:
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def span_ns(ops: list) -> float:
    """From the first op's start to the last op's end, idle gaps and all."""
    if not ops:
        return 0.0
    return max(start + dur for _, start, dur, _ in ops) - ops[0][1]


def idle_gaps(ops: list) -> list:
    """(start_ns, length_ns) of each gap between the ops' intervals."""
    gaps, end = [], None
    for _, start, dur, _ in ops:
        if end is not None and start > end:
            gaps.append((end, start - end))
        end = start + dur if end is None else max(end, start + dur)
    return gaps


def host_activity(host: list, t_ns: float) -> str:
    """The innermost host event running at `t_ns`, as `thread: name`."""
    best = None
    for thread, name, start, dur in host:
        if start <= t_ns <= start + dur and (best is None or dur < best[0]):
            best = (dur, f"{thread.split('/')[0]}: {name}")
    return best[1] if best else "host: no event"


@dataclass
class Context:
    """What a per-layer metric's reader may read."""
    events: dict
    chips: int
    steps: int              # steps in the traced window
    window_s: float         # host clock, blocked at both ends
    cell: dict
    config: dict
    trainer_flags: dict     # the cell's flags, parsed (run.parse_flags)
    device_kind: str
    peaks: dict
    load_module: object
    variance: list = field(default_factory=list)

    def __post_init__(self):
        planes = list(self.events["devices"].values())[:self.chips]
        self.device_ops = planes
        self.busy_s = (sum(busy_ns(p) for p in planes) / len(planes) / 1e9
                       if planes else 0.0)
        self.span_s = (sum(span_ns(p) for p in planes) / len(planes) / 1e9
                       if planes else 0.0)

    def peak(self, name: str) -> float:
        if self.device_kind not in self.peaks:
            raise KeyError(f"device {self.device_kind!r} is not in "
                           f"bench/peaks.json")
        return float(self.peaks[self.device_kind][name])

    def kernel_seconds(self, name: str) -> tuple[float, int]:
        """Device seconds and launches of the Pallas kernel `name`,
        averaged over the chips."""
        total, n = 0.0, 0
        for ops in self.device_ops:
            for op, _, dur, kernel in ops:
                if kernel and op.split(".")[0] == name:
                    total += dur
                    n += 1
        k = max(len(self.device_ops), 1)
        return total / 1e9 / k, n // k

    def breakdown(self) -> dict:
        """The device ops that took most time, and the longest idle gaps
        by what the host was doing in them (first chip)."""
        per_op: dict = {}
        for ops in self.device_ops:
            for op, _, dur, _ in ops:
                per_op[op] = per_op.get(op, 0.0) + dur / 1e9
        k = max(len(self.device_ops), 1)
        top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(idle_gaps(self.device_ops[0]) if self.device_ops
                      else [], key=lambda g: -g[1])[:TOP]
        return {"device_ops": [[op, s / k] for op, s in top],
                "idle_gaps": [[host_activity(self.events["host"],
                                             start + length / 2),
                               length / 1e9] for start, length in gaps]}
