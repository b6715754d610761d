"""Profiler trace -> device busy time, program time, top operations and
idle gaps labelled by the benchmark's host spans.

The JAX profiler writes an ``.xplane.pb``; `jax.profiler.ProfileData`
reads it.  On a TPU each chip is a plane named ``/device:TPU:<n>`` whose
``XLA Ops`` line holds one event per operation run and whose ``XLA
Modules`` line holds one event per program run.  A trace without a
device plane is refused, unless the caller asks for ``host_ops``: then
(the CPU tests do so) the host's XLA operations, events that carry an
``hlo_op`` stat and whose program is the ``hlo_module`` stat, stand in
for one chip.

The benchmark brackets what it traces with a host span named
``bench.traced`` and marks what the host is doing with spans named
``bench.<what>`` (`jax.profiler.TraceAnnotation`).  Every number here is
taken inside ``bench.traced``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

TRACED = "bench.traced"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    window_s: float                 # length of the traced window
    busy_s: float                   # union of device-op intervals, per chip
    chips: int
    module_s: dict                  # program name -> device seconds, per chip
    module_runs: dict               # program name -> runs, per chip
    top_ops: list                   # [(op name, device seconds per chip)]
    gaps: list                      # [(host span, idle seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, fragment: str) -> tuple:
        """(device seconds, runs) of the programs whose name holds
        `fragment`, per chip."""
        s = sum(v for k, v in self.module_s.items() if fragment in k)
        n = sum(v for k, v in self.module_runs.items() if fragment in k)
        return s, n


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_trace(path: str, *, top: int = 10,
                 host_ops: bool = False) -> Reduced:
    """Reads one ``.xplane.pb`` and reduces it to per-chip numbers.
    Without a device plane it raises, unless `host_ops` is set."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, window = [], None
    chips = []        # per chip: (op events, module events)
    host_events = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            if ops:
                chips.append((ops, mods))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == TRACED:
                        window = iv
                    else:
                        spans.append((*iv, e.name[len(SPAN_PREFIX):]))
                elif (host_ops and e.duration_ns > 0
                      and line.name.startswith("tf_XLA")):
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        host_events.append((
                            e.start_ns, e.start_ns + e.duration_ns,
                            str(stats["hlo_op"]),
                            (str(stats.get("hlo_module")),
                             stats.get("run_id"))))
    if window is None:
        raise RuntimeError(f"the trace has no {TRACED!r} span")
    lo, hi = window
    if not chips and host_events:   # asked for: the host as one "chip"
        mods = {}
        for s, e, _, m in host_events:
            mods.setdefault(m, []).append((s, e))
        chips = [([(s, e, n) for s, e, n, _ in host_events],
                  [(min(s for s, _ in iv), max(e for _, e in iv), m[0])
                   for m, iv in mods.items()])]
    if not chips:
        raise RuntimeError("the trace holds no device operation; planes: "
                           + "; ".join(f"{p.name}: {[l.name for l in p.lines]}"
                                       for p in data.planes))

    busy_total, module_s, module_runs, op_s = 0.0, {}, {}, {}
    idle = None
    for ops, mods in chips:
        inside = _clip([(s, e) for s, e, _ in ops], lo, hi)
        merged = _union(inside)
        busy_total += sum(e - s for s, e in merged) / 1e9
        for s, e, name in ops:
            if e > lo and s < hi:
                op_s[name] = op_s.get(name, 0.0) + (min(e, hi)
                                                    - max(s, lo)) / 1e9
        for s, e, name in mods:
            if e > lo and s < hi:
                key = name.split("(")[0]
                module_s[key] = module_s.get(key, 0.0) + (
                    min(e, hi) - max(s, lo)) / 1e9
                module_runs[key] = module_runs.get(key, 0) + 1
        if idle is None:  # gaps are read on the first chip
            idle, t = [], lo
            for s, e in merged:
                if s > t:
                    idle.append((t, s))
                t = max(t, e)
            if t < hi:
                idle.append((t, hi))
    n = len(chips)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    gaps = [(_label(s, e, spans), (e - s) / 1e9) for s, e in longest]
    tops = sorted(((k, v / n) for k, v in op_s.items()),
                  key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy_total / n,
                   chips=n, module_s={k: v / n for k, v in module_s.items()},
                   module_runs={k: v // n for k, v in module_runs.items()},
                   top_ops=tops, gaps=gaps)


def _label(s, e, spans) -> str:
    """The innermost benchmark host span open at the gap's midpoint."""
    mid = (s + e) / 2
    best = None
    for a, b, name in spans:
        if a <= mid < b and (best is None or b - a < best[1] - best[0]):
            best = (a, b, name)
    return best[2] if best else "other"


class Profile:
    """Traces the device while open; the host spans of the benchmark
    (`span`) and the ``bench.traced`` bracket land in the same trace.
    The Python tracer stays off: it would slow the host code measured."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._traced = None

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._traced = span("traced")
        self._traced.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self._traced.__exit__(*exc)
        jax.profiler.stop_trace()


def span(what: str):
    """A host span ``bench.<what>`` in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + what)
