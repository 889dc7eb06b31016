"""Reduction of a JAX profiler trace to device busy time, kernel time and
the longest idle gaps, attributed to the harness's own spans.

`extract` reads the `.xplane.pb` the profiler wrote into plain events:
device operations from the TPU planes' "XLA Ops" lines, and the
harness's spans (`client.*`, `scheduler.poll`) from the host plane, all
on the profiler's one clock. `reduce` works on that plain form, so a
small trace recorded on the chip is kept as a test fixture.
"""
from __future__ import annotations

from pathlib import Path

# Kernel ops by name. The Pallas kernels carry no name of their own in the
# trace yet (a `tpu_custom_call` whose label is HLO text), so they are told
# apart by shape: the packed attention returns a pair of int32 limb-sum
# arrays, every binary GEMM one int32 or uint32 array. Named kernels
# (`pallas_call(name=...)`) would match the first patterns.
KERNELS = {
    "attn": ("_paged_kernel", "packed_attention", "= (s32[2,"),
    "gemm": ("_vpu_kernel", "_fused_epilogue_kernel", "binary_gemm",
             "tpu_custom_call"),
}
CONTAINERS = ("%while", "%conditional", "%call.")   # ops holding others
HARNESS_SPANS = ("client.sleep", "client.submit", "scheduler.poll")
OPS_LINE = "XLA Ops"
TOP = 10
NAME_CHARS = 160        # an op's label is its HLO text; keep its head


def short(name: str) -> str:
    tag = " [tpu_custom_call]" if "tpu_custom_call" in name[NAME_CHARS:] \
        else ""
    return name[:NAME_CHARS] + tag


def kind_of(name: str) -> str | None:
    for kind, pats in KERNELS.items():
        if any(p in name for p in pats):
            return kind
    return None


def extract(tdir) -> dict:
    """{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "spans":
    [[name, start_ns, dur_ns], ...]} from the newest trace under tdir."""
    from jax.profiler import ProfileData
    pbs = sorted(Path(tdir).rglob("*.xplane.pb"))
    if not pbs:
        return {"devices": {}, "spans": []}
    pd = ProfileData.from_file(str(pbs[-1]))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices[plane.name] = [[short(e.name), e.start_ns,
                                        e.duration_ns]
                                       for e in lines[OPS_LINE].events]

        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HARNESS_SPANS:
                        spans.append([e.name, e.start_ns, e.duration_ns])
    return {"devices": {k: v for k, v in devices.items() if v},
            "spans": spans}


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(events: dict, window_s: float) -> dict | None:
    """Busy seconds (union of op intervals, averaged over the chips),
    seconds in each kernel kind, the ops that took most time (loops and
    calls, which hold other ops, left out), and the longest idle gaps by
    the harness span that overlaps each most."""
    devices = events["devices"]
    if not devices:
        return None
    busy, kernel_s, per_op = 0.0, {k: 0.0 for k in KERNELS}, {}
    gaps = []
    for ops in devices.values():
        merged = _union([(s, s + d) for _, s, d in ops])
        busy += sum(b - a for a, b in merged) / 1e9
        for name, _, d in ops:
            if name.startswith(CONTAINERS):
                continue
            per_op[name] = per_op.get(name, 0.0) + d / 1e9
            kind = kind_of(name)
            if kind:
                kernel_s[kind] += d / 1e9
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
    n = len(devices)
    spans = events["spans"]

    def owner(a: float, b: float) -> str:
        best, cover = "untraced host work", 0.0
        for name, s, d in spans:
            c = min(b, s + d) - max(a, s)
            if c > cover:
                best, cover = name, c
        return best

    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[owner(a, b), (b - a) / 1e9] for a, b in gaps[:TOP]]
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": busy / n, "window_s": window_s,
            "kernel_s": {k: v / n for k, v in kernel_s.items()},
            "top_ops": [[k, v / n] for k, v in top], "idle_gaps": idle}


def reduce_dir(tdir, window_s: float):
    return reduce(extract(tdir), window_s)
