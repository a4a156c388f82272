"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
metrics read: device busy and idle time, device time per operation, the
grouped-matmul kernel's events, and the longest idle gaps, each put down
to what the host was doing then (the harness's `chipbench.*` spans).

The window is the host span `chipbench.window`. Each device's busy time
is the union of the intervals of its operations ("XLA Ops" lines of the
`/device:TPU:<n>` planes) inside the window; idle is the rest. Device
and host events are on one clock in the trace.

On a TPU the trace names a device op by its HLO text
(`%moe_gmm_pallas.7 = bf16[...] custom-call(...), ...`); `op_name` takes
the instruction's name from it. A loop's op (`while`) is on the same line
as the ops of its body, which lie inside it; each op's time in the
breakdown is its own, without the ops nested in it.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
# the grouped-matmul kernel: the Pallas call of repro.kernels.moe_gmm. The
# compiled program names that custom call (custom_call_target
# "tpu_custom_call") after the jitted moe_gmm_pallas, moe_gmm_pallas.<n>,
# and the trace's device op is named as the instruction. Only the op's own
# name is matched: the pads and slices around the call carry the jit's
# name in their metadata, and are not the kernel.
KERNELS = {"moe_gmm": re.compile(r"^moe_gmm_pallas(\.\d+)?$")}
_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


def find_trace(log_dir) -> Path:
    paths = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(paths[-1])


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(name: str) -> str:
    """The instruction's name, from an event named by it or by its HLO
    text: `%fusion.12 = bf16[8] fusion(...)` -> fusion.12."""
    m = _INSTRUCTION.match(name)
    return m.group(1) if m else name


def op_family(name: str) -> str:
    """An op's name without its suffixes: fusion.12 -> fusion,
    dynamic-slice_fusion.14.remat -> dynamic-slice_fusion."""
    return name.split(".", 1)[0] or name


def _own_times(iv: List[Tuple[float, float, str]]) -> List[Tuple[str, float]]:
    """(name, time) of each interval less the intervals nested in it."""
    order = sorted(range(len(iv)), key=lambda i: (iv[i][0], -iv[i][1]))
    own = [b - a for a, b, _ in iv]
    stack: List[int] = []
    for i in order:
        a, b, _ = iv[i]
        while stack and iv[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, iv[stack[-1]][1]) - a
        stack.append(i)
    return [(iv[i][2], own[i]) for i in range(len(iv))]


def load(path):
    """A trace file (`.xplane.pb`, or gzipped `.xplane.pb.gz`)."""
    from jax.profiler import ProfileData
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(str(path))


def reduce(path, kernels=KERNELS) -> Optional[dict]:
    return reduce_profile(load(path), kernels)


def reduce_profile(pd, kernels=KERNELS) -> Optional[dict]:
    """The trace's numbers, or None where it holds no window or no device
    operation in it. Times are in seconds."""
    spans: List[Tuple[float, float, str]] = []
    devices: Dict[str, List] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [ev for line in plane.lines
                                   if line.name == OP_LINE
                                   for ev in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    if not win or not devices:
        return None
    lo, hi = win[0][0], win[0][1]
    window_s = (hi - lo) * 1e-9

    busy, ops, kernel_s, kernel_n = [], defaultdict(float), \
        defaultdict(float), defaultdict(int)
    gaps = defaultdict(float)
    inner = sorted(s for s in spans if s[2] != WINDOW_SPAN)
    starts = [s[0] for s in inner]
    longest = max((s[1] - s[0] for s in inner), default=0)
    for evs in devices.values():
        iv = []
        for ev in evs:
            a, b = ev.start_ns, ev.start_ns + ev.duration_ns
            if b <= lo or a >= hi:
                continue
            iv.append((max(a, lo), min(b, hi), op_name(ev.name)))
        for name, d in _own_times(iv):
            ops[op_family(name)] += d * 1e-9
            for k, pat in kernels.items():
                if pat.match(name):
                    kernel_s[k] += d * 1e-9
                    kernel_n[k] += 1
        merged = _union([(a, b) for a, b, _ in iv])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[_host_doing(a, b, inner, starts, longest)] += (b - a) * 1e-9
    n = len(devices)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "devices": n,
        "ops": {k: v / n for k, v in ops.items()},
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "kernel_events": dict(kernel_n),
        "idle_by_span": {k: v / n for k, v in gaps.items()},
    }


def _host_doing(a, b, spans, starts, longest) -> str:
    """The shortest harness span that covers half of the gap [a, b] or
    more; failing that, the one that covers most of it."""
    lo = bisect.bisect_left(starts, a - longest)
    hi = bisect.bisect_left(starts, b)
    best, cover, best_len = WINDOW_SPAN, 0.0, None
    for s0, s1, name in spans[lo:hi]:
        c = min(b, s1) - max(a, s0)
        if c >= 0.5 * (b - a) and (best_len is None or s1 - s0 < best_len):
            best, cover, best_len = name, c, s1 - s0
        elif best_len is None and c > cover:
            best, cover = name, c
    return best


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
