"""Reduction of a profiler trace to device busy time, per-program and
per-kernel time, and idle gaps set against the harness's host spans.

`load` reads the `.xplane.pb` that `jax.profiler` writes and turns it into a
plain `Trace`: per device, its op intervals and its program (XLA module)
intervals, and the host spans the harness annotated. Everything after that
is arithmetic on sorted intervals, kept here so that every PR computes the
same numbers the same way. Times are in seconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
from pathlib import Path

# host spans the harness writes around its calls into the program
HOST_SPANS = ("generator.wait", "engine.submit", "engine.step")


@dataclasses.dataclass
class Device:
    ops: list          # [(name, start_s, end_s)] device operations
    programs: list     # [(name, start_s, end_s)] program executions


@dataclasses.dataclass
class Trace:
    devices: list      # [Device] one per chip that ran anything
    host: list         # [(name, start_s, end_s)] harness host spans

    def window(self) -> tuple:
        """The traced window: first to last harness host span."""
        return (min(s for _, s, _ in self.host),
                max(e for _, _, e in self.host))


# --------------------------------------------------------------------------
# reading
# --------------------------------------------------------------------------
def _short(name: str) -> str:
    """An XLA op's event name is its whole HLO instruction; keep the
    instruction's name, and mark a Mosaic kernel (a Pallas kernel has no
    name of its own in the trace)."""
    head = name.split(" = ", 1)[0]
    if 'custom_call_target="tpu_custom_call"' in name:
        head += " tpu_custom_call"
    return head


def _events(line, short=False):
    for ev in line.events:
        s = ev.start_ns * 1e-9
        yield (_short(ev.name) if short else ev.name), s, \
            s + ev.duration_ns * 1e-9


def from_profile(prof) -> Trace:
    """A `jax.profiler.ProfileData` as a `Trace`. On a TPU plane the
    "XLA Ops" line holds the operations and "XLA Modules" the programs."""
    devices, host = [], []
    for plane in prof.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            ops = sorted(_events(lines["XLA Ops"], short=True),
                         key=lambda e: e[1])
            progs = sorted(_events(lines["XLA Modules"]), key=lambda e: e[1]) \
                if "XLA Modules" in lines else []
            if ops:
                devices.append(Device(ops, progs))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln) if e[0] in HOST_SPANS)
    return Trace(devices, sorted(host, key=lambda e: e[1]))


def load(trace_dir) -> Trace:
    """The newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


def dump(trace: Trace) -> str:
    """JSON of a Trace (small recorded traces for tests)."""
    return json.dumps({"devices": [dataclasses.asdict(d)
                                   for d in trace.devices],
                       "host": trace.host})


def undump(text: str) -> Trace:
    d = json.loads(text)
    tup = lambda xs: [tuple(x) for x in xs]
    return Trace([Device(tup(x["ops"]), tup(x["programs"]))
                  for x in d["devices"]], tup(d["host"]))


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------
def union(intervals, lo: float, hi: float) -> list:
    """Sorted disjoint (start, end) covering `intervals` clipped to
    [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(covered, lo: float, hi: float) -> list:
    """Complement of sorted disjoint `covered` within [lo, hi]."""
    out, t = [], lo
    for s, e in covered:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a, b) -> float:
    """Total overlap of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------
def busy(dev: Device, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which some operation ran on `dev`."""
    return length(union([(s, e) for _, s, e in dev.ops], lo, hi))


def busy_by_program(dev: Device, lo: float, hi: float) -> dict:
    """{program name: busy seconds of the operations inside its
    executions}, within [lo, hi]. An operation belongs to the program
    execution that holds its start; names lose their "(id)" suffix."""
    starts = [s for _, s, _ in dev.programs]
    spans: dict = {}
    for name, s, e in dev.ops:
        k = bisect.bisect_right(starts, s) - 1
        prog = "(none)"
        if k >= 0 and dev.programs[k][2] >= s:
            prog = dev.programs[k][0].split("(")[0]
        spans.setdefault(prog, []).append((s, e))
    return {p: length(union(iv, lo, hi)) for p, iv in spans.items()}


def leaves(dev: Device) -> list:
    """The operations that hold no other operation (a loop or call on the
    ops line holds the ops of its body)."""
    ops = dev.ops
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= o[2]]


def op_time(dev: Device, lo: float, hi: float, match) -> float:
    """Summed device time of leaf operations whose name satisfies
    `match`."""
    return sum(min(e, hi) - max(s, lo) for n, s, e in leaves(dev)
               if match(n) and min(e, hi) > max(s, lo))


def top_ops(dev: Device, lo: float, hi: float, n: int = 10) -> list:
    """[["program/op", seconds]] of the `n` leaf operations that took
    most time, each named with the program it ran in."""
    starts = [s for _, s, _ in dev.programs]
    tot: dict = {}
    for name, s, e in leaves(dev):
        d = min(e, hi) - max(s, lo)
        if d <= 0:
            continue
        k = bisect.bisect_right(starts, s) - 1
        prog = dev.programs[k][0].split("(")[0] if k >= 0 else "(none)"
        key = f"{prog}/{name}"
        tot[key] = tot.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host(trace: Trace, dev: Device, lo: float, hi: float,
                 n: int = 10) -> list:
    """[[host activity, seconds]]: the device's idle time in [lo, hi] split
    by the harness host span running meanwhile ("other" where none ran)."""
    idle = gaps(union([(s, e) for _, s, e in dev.ops], lo, hi), lo, hi)
    out, covered = {}, []
    for name in HOST_SPANS:
        spans = union([(s, e) for m, s, e in trace.host if m == name], lo, hi)
        covered.extend(spans)
        t = overlap(idle, spans)
        if t > 0:
            out[name] = t
    rest = length(idle) - overlap(idle, union(covered, lo, hi))
    if rest > 0:
        out["other"] = rest
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:n]]


def load_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of `device_kind` from peaks.json; an unknown
    device is an error, never a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table["devices"][device_kind]
