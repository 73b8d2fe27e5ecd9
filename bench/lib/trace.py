"""Profiler trace of a run's window, reduced to metrics.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line
holds one event per executed operation and ``XLA Modules`` one per
executed program.  Host spans that the benchmark writes
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines.
All times below are seconds.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import tempfile

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|send|recv", re.I)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
PROGRAM_ID_RE = re.compile(r"\(\d+\)$")


def op_name(hlo: str) -> str:
    """``%fusion.9 = f32[...] fusion(...)`` -> ``fusion.9``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def program_name(module: str) -> str:
    """``jit__round_local(1699...)`` -> ``jit__round_local``."""
    return PROGRAM_ID_RE.sub("", module)


@contextlib.contextmanager
def profiled(enabled: bool, keep: str | None = None):
    """Trace the body when ``enabled``; yields a holder whose ``path`` is
    the xplane file once the body ends.  The directory is removed after
    reading unless ``keep`` names where to copy it."""
    holder = {"path": None, "dir": None}
    if not enabled:
        yield holder
        return
    import jax
    d = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0         # host spans only, no Python calls
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield holder
    finally:
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        holder["path"] = found[0] if found else None
        holder["dir"] = d
        if keep and found:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(found[0], os.path.join(keep, "trace.xplane.pb"))


def cleanup(holder) -> None:
    if holder.get("dir"):
        shutil.rmtree(holder["dir"], ignore_errors=True)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


class Trace:
    """The events of one xplane file, in seconds on the profiler's clock."""

    def __init__(self, planes):
        self.ops: dict[int, list] = {}       # device -> [(name, t0, t1)]
        self.modules: dict[int, list] = {}   # device -> [(name, t0, t1)]
        self.host: list = []                 # [(name, t0, t1)]
        for pl in planes:
            m = DEVICE_PLANE_RE.match(pl.name)
            for ln in pl.lines:
                evs = [(ev.name, ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9)
                       for ev in ln.events]
                if m and ln.name == OPS_LINE:
                    self.ops.setdefault(int(m.group(1)), []).extend(evs)
                elif m and ln.name == MODULES_LINE:
                    self.modules.setdefault(int(m.group(1)), []).extend(evs)
                elif pl.name.startswith("/host:"):
                    self.host.extend(e for e in evs if e[2] > e[1])

    @classmethod
    def load(cls, path: str) -> "Trace":
        import jax
        return cls(jax.profiler.ProfileData.from_file(path).planes)

    def spans(self, name: str) -> list:
        return sorted((s, e) for n, s, e in self.host if n == name)

    def window(self, name: str = "bench.window") -> tuple[float, float]:
        sp = self.spans(name)
        if not sp:
            raise ValueError(f"no host span {name!r} in the trace")
        return sp[0][0], sp[-1][1]

    def busy(self, dev: int, t0: float, t1: float) -> list:
        ivs = ((s, e) for _, s, e in self.ops.get(dev, []))
        return _union(_clip(ivs, t0, t1))

    def busy_s(self, t0: float, t1: float) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(d, t0, t1))
                   for d in self.ops) / len(self.ops)

    def top_ops(self, t0: float, t1: float, n: int = 10) -> list:
        """[["program/op", seconds]] of the ops that took most device time,
        averaged over devices; an op is named by the program it ran in."""
        tot: dict[str, float] = {}
        for dev, evs in self.ops.items():
            mods = sorted(self.modules.get(dev, []), key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for name, s, e in _clip3(evs, t0, t1):
                i = bisect.bisect_right(starts, s) - 1
                prog = (program_name(mods[i][0])
                        if i >= 0 and mods[i][2] >= s else "?")
                key = f"{prog}/{op_name(name)}"
                tot[key] = tot.get(key, 0.0) + (e - s)
        nd = max(1, len(self.ops))
        return [[k, v / nd] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def module_s(self, t0: float, t1: float) -> dict:
        """Device seconds per compiled program (name with its id),
        averaged over devices."""
        tot: dict[str, float] = {}
        for evs in self.modules.values():
            for name, s, e in _clip3(evs, t0, t1):
                tot[name] = tot.get(name, 0.0) + (e - s)
        nd = max(1, len(self.modules))
        return {k: v / nd for k, v in tot.items()}

    def collective_s(self, t0: float, t1: float) -> float:
        """Seconds of collective ops, averaged over devices."""
        if not self.ops:
            return 0.0
        tot = sum(e - s for evs in self.ops.values()
                  for name, s, e in _clip3(evs, t0, t1)
                  if COLLECTIVE_RE.search(name))
        return tot / len(self.ops)

    def idle_gaps(self, t0: float, t1: float, n: int = 10,
                  prefix: str = "bench.", spans=None) -> list:
        """[[host span, seconds]]: device-0 idle time inside the window,
        each idle instant put down to the innermost benchmark span open at
        it (the trace's ``bench.*`` spans plus ``spans``, ``(name, t0, t1)``
        on the trace clock), summed per span name, longest first."""
        dev = min(self.ops) if self.ops else None
        busy = self.busy(dev, t0, t1) if dev is not None else []
        gaps, cur = [], t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < t1:
            gaps.append((cur, t1))
        spans = [(nm, s, e) for nm, s, e in self.host
                 if nm.startswith(prefix)] + list(spans or [])
        tot: dict[str, float] = {}
        for g0, g1 in gaps:
            inside = [sp for sp in spans if sp[1] < g1 and sp[2] > g0]
            cuts = sorted({g0, g1} | {x for _, s, e in inside
                                      for x in (s, e) if g0 < x < g1})
            for a, b in zip(cuts, cuts[1:]):
                open_ = [(e - s, nm) for nm, s, e in inside
                         if s <= a and e >= b]
                label = min(open_)[1] if open_ else "(no benchmark span)"
                tot[label] = tot.get(label, 0.0) + (b - a)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _clip3(evs, t0, t1):
    return [(n, max(s, t0), min(e, t1)) for n, s, e in evs
            if e > t0 and s < t1]
