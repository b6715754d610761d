"""Profiler trace -> the program's own scopes and spans, inside the
window `trace.reduce_trace` reads (``bench.traced``).

- Device seconds under each `jax.named_scope` of one compiled program,
  per chip: the union of the intervals of the operations whose name
  stack holds the scope, so a ``while`` and the operations of its body
  count once.  JAX wraps a scope in ``jvp(...)`` and ``transpose(...)``
  on the gradient's forward and backward passes; those wrappers are
  unwrapped, so both passes count.
- Host seconds inside each ``repro.<what>`` span of the program and each
  ``bench.<what>`` span of the benchmark.
- Idle device seconds under the innermost open ``repro.`` span (first
  chip), and the longest idle gaps split the same way.

Which scope an operation ran in is read from the program's compiled HLO
text (`op_scopes`): the ``op_name`` metadata of the instruction that the
trace event names.  The trace cannot give it: a TPU v5e ``XLA Ops``
event carries only ``device_offset_ps``, ``device_duration_ps`` and
``Time Scale Multiplier`` and is named by the instruction's text without
its metadata, the CPU's host events carry ``hlo_op``/``hlo_module``,
and the ``/host:metadata`` plane is empty on both.  That one path serves
the chip and the CPU tests.  Device planes and the CPU stand-in are read
as `trace.reduce_trace` reads them.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

from bench.harness.trace import SPAN_PREFIX, TRACED, _clip, _union

PROGRAM_PREFIX = "repro."
_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?'
                      r'metadata=\{[^}]*op_name="([^"]*)"')
_WRAPPER = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def op_scopes(hlo_text: str) -> dict:
    """instruction name -> frozenset of the scope names on its
    ``op_name`` name stack: every part but the last, which names the
    primitive (``jvp(...)``/``transpose(...)`` unwrapped; a fused
    instruction's ``;``-joined stacks all count), and each two adjacent
    parts joined by ``/``, so that a set's time in one round reads as
    ``round_0/paper``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if not m:
            continue
        names = set()
        for stack in m.group(2).split(";"):
            parts = []
            for part in stack.split("/")[:-1]:
                while (w := _WRAPPER.match(part)) is not None:
                    part = w.group(1)
                parts.append(part)
            names.update(parts)
            names.update(f"{a}/{b}" for a, b in zip(parts, parts[1:]))
        out[m.group(1)] = frozenset(names)
    return out


def instruction(event_name: str) -> str:
    """The HLO instruction an operation event ran: the event's name up
    to the first blank or ``=``.  A chip's events are named by the
    instruction's text (``%fusion.3 = f32[...] fusion(...)``), the CPU's
    by the instruction's name alone."""
    return re.split(r"[\s=]", event_name.lstrip("%"), maxsplit=1)[0]


@dataclasses.dataclass
class Scoped:
    window_s: float
    chips: int
    program_s: float                # union of the program's ops, per chip
    span_s: dict                    # host span -> seconds in the window
    idle_under: dict                # innermost repro. span -> idle seconds
    gaps: list                      # [(idle s, {span: s})], longest first
    # scope -> per chip, the merged intervals of its operations
    scope_iv: dict = dataclasses.field(repr=False, default_factory=dict)

    def scope_seconds(self, *scopes: str) -> float:
        """Device seconds per chip under any of `scopes`: the union of
        their operations' intervals."""
        total = 0.0
        for chip in range(self.chips):
            total += sum(e - s for s, e in _union(
                [iv for sc in scopes if sc in self.scope_iv
                 for iv in self.scope_iv[sc][chip]])) / 1e9
        return total / self.chips if self.chips else 0.0


def reduce_scopes(path: str, scopes_of: dict, *, program: str,
                  host_ops: bool = False, top: int = 10) -> Scoped:
    """Reads one ``.xplane.pb``.  `scopes_of` is `op_scopes` of the
    compiled program whose runs' names hold `program`; an operation is
    the program's when its midpoint lies in one of those runs."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window, spans, chips, host_events = None, [], [], []
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
                iv = (e.start_ns, e.start_ns + e.duration_ns)
                if e.name == TRACED:
                    window = iv
                elif e.name.startswith((PROGRAM_PREFIX, SPAN_PREFIX)):
                    spans.append((*iv, e.name))
                elif (host_ops and e.duration_ns > 0
                      and line.name.startswith("tf_XLA")):
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        host_events.append((
                            *iv, e.name, (str(stats.get("hlo_module")),
                                          stats.get("run_id"))))
    if window is None:
        raise RuntimeError(f"the trace has no {TRACED!r} span")
    if not chips and host_events:   # asked for: the host as one "chip"
        runs = {}
        for s, e, _, m in host_events:
            runs.setdefault(m, []).append((s, e))
        chips = [([(s, e, n) for s, e, n, _ in host_events],
                  [(min(s for s, _ in iv), max(e for _, e in iv), m[0])
                   for m, iv in runs.items()])]
    if not chips:
        raise RuntimeError("the trace holds no device operation")
    lo, hi = window

    scope_iv, program_s, idle = {}, 0.0, None
    for chip, (ops, mods) in enumerate(chips):
        runs = _union((s, e) for s, e, name in mods if program in name)
        starts = [s for s, _ in runs]
        mine, by_scope = [], {}
        for s, e, name in ops:
            if e <= lo or s >= hi:
                continue
            k = bisect.bisect_right(starts, (s + e) / 2) - 1
            if k < 0 or (s + e) / 2 >= runs[k][1]:
                continue
            iv = (max(s, lo), min(e, hi))
            mine.append(iv)
            for scope in scopes_of.get(instruction(name), ()):
                by_scope.setdefault(scope, []).append(iv)
        program_s += sum(e - s for s, e in _union(mine)) / 1e9
        for scope, ivs in by_scope.items():
            scope_iv.setdefault(scope, [[] for _ in chips])[chip] = \
                _union(ivs)
        if idle is None:  # gaps are read on the first chip
            busy = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
            idle, t = [], lo
            for s, e in busy:
                if s > t:
                    idle.append((t, s))
                t = max(t, e)
            if t < hi:
                idle.append((t, hi))

    span_s = {}
    for s, e, name in spans:
        for a, b in _clip([(s, e)], lo, hi):
            span_s[name] = span_s.get(name, 0.0) + (b - a) / 1e9
    cuts, labels = _timeline(
        [sp for sp in spans if sp[2].startswith(PROGRAM_PREFIX)])
    idle_under, split = {}, []
    for s, e in idle:
        parts = _split(s, e, cuts, labels)
        split.append(((e - s) / 1e9, parts))
        for label, secs in parts.items():
            idle_under[label] = idle_under.get(label, 0.0) + secs
    n = len(chips)
    return Scoped(window_s=(hi - lo) / 1e9, chips=n, program_s=program_s / n,
                  span_s=span_s, idle_under=idle_under,
                  gaps=sorted(split, key=lambda g: -g[0])[:top],
                  scope_iv=scope_iv)


def _timeline(spans) -> tuple:
    """(cuts, labels): between cuts[i] and cuts[i + 1] the innermost
    of `spans` open is labels[i] ("other" where none is)."""
    cuts = sorted({t for a, b, _ in spans for t in (a, b)})
    labels = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        best = None
        for x, y, name in spans:
            if x <= mid < y and (best is None or y - x < best[1] - best[0]):
                best = (x, y, name)
        labels.append(best[2] if best else "other")
    return cuts, labels


def _split(s, e, cuts, labels) -> dict:
    """Seconds of [s, e) under each label of the timeline."""
    out = {}
    i = bisect.bisect_right(cuts, s) - 1
    t = s
    while t < e:
        end = min(e, cuts[i + 1]) if i + 1 < len(cuts) else e
        label = labels[i] if 0 <= i < len(labels) else "other"
        out[label] = out.get(label, 0.0) + (end - t) / 1e9
        t, i = end, i + 1
    return out


def layer_metrics(sc: Scoped, *, traced_steps: int,
                  window_compiles=None) -> dict:
    """The per-layer numbers the program's scopes, spans and compile
    counter give, per traced step.  A scope or span the program does not
    have gives no number (a program without them gives none)."""
    per_step = 1e3 / traced_steps
    out = {}
    for metric, scope in (("optimizer_device_ms.train", "optimizer"),
                          ("gnn_device_ms.train", "gnn"),
                          ("pool_device_ms.train", "pool")):
        if scope in sc.scope_iv:
            out[metric] = per_step * sc.scope_seconds(scope)
    for metric, span in (("sample_ms.train", "repro.sample"),
                         ("merge_pad_ms.train", "repro.merge_pad")):
        if span in sc.span_s:
            out[metric] = per_step * sc.span_s[span]
    if "repro.merge_pad" in sc.span_s:
        out["input_idle_share.train"] = 100.0 * sum(
            sc.idle_under.get(s, 0.0)
            for s in ("repro.sample", "repro.merge_pad")) / sc.window_s
    if window_compiles is not None:
        out["step_compiles.train"] = window_compiles
    return out
