"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the arithmetic can be checked on a small recorded trace:

* ``events_from_xplane(path, hlo_scopes)`` reads the ``.xplane.pb`` that
  ``jax.profiler`` writes and returns plain event lists: the device
  operations of each chip (name, start, duration, program, name scope) and
  the host spans the benchmark's own files open with
  ``jax.profiler.TraceAnnotation`` (``bench:*``).
* ``reduce(events)`` turns those lists into a ``Summary``: the traced
  window, the device's busy time (the union of its operation intervals,
  averaged over the chips), time per name scope, per program and per
  operation, and the idle gaps with what the host was doing in each.

Times are nanoseconds on the profiler's clock.  A device operation's name
scope is the ``op_name`` metadata of its HLO instruction, which carries
the ``jax.named_scope`` path (``.../quant_act_fused/k_fused_quantize/...``).
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from typing import Iterable, Optional

WINDOW_SPAN = "bench:window"
HOST_PREFIX = "bench:"
# Lines of a TPU device plane that hold one event per executed operation.
_OPS_LINES = ("XLA Ops",)
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_WRAPPER = re.compile(r"^(?:[\w\-]+\()+")
# Control flow: the event of a while loop or a conditional spans the
# events of the operations its body runs, which the trace also holds.
_CONTROL = re.compile(r"^(while|conditional|call)(\.\d+)?$")


@dataclasses.dataclass
class Events:
    """Plain event lists; ``ops[d]`` are device ``d``'s operations as
    ``[name, start_ns, dur_ns, program, scope]``, ``host`` the benchmark's
    host spans as ``[name, start_ns, dur_ns]``."""
    ops: dict
    host: list

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump({"ops": self.ops, "host": self.host}, f)

    @classmethod
    def from_json(cls, path: str) -> "Events":
        with gzip.open(path, "rt") as f:
            d = json.load(f)
        return cls(ops=d["ops"], host=d["host"])


def hlo_scopes(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of an optimized HLO module's text."""
    out = {}
    pat = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*"
                     r"op_name=\"([^\"]*)\"")
    for line in hlo_text.splitlines():
        m = pat.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def _op_name(event_name: str) -> str:
    """``fusion.71`` of ``%fusion.71 = f32[..] fusion(..), ...`` (a TPU
    trace names each operation by its HLO text)."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _modules(line) -> list:
    """``[(start, end, program)]`` of an ``XLA Modules`` line, sorted."""
    return sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                   ev.name.split("(", 1)[0]) for ev in line.events)


def events_from_xplane(path: str, scopes: Optional[dict] = None) -> Events:
    """Read a profiler ``.xplane.pb``.  ``scopes`` maps a program name
    (``jit_train_step``) to ``hlo_scopes`` of that program's HLO; an
    operation runs in the program whose ``XLA Modules`` event spans it."""
    from jax.profiler import ProfileData

    scopes = scopes or {}
    data = ProfileData.from_file(path)
    ops: dict = {}
    host: list = []
    for plane in data.planes:
        dev = _DEVICE_PLANE.match(plane.name)
        if dev:
            lines = {line.name: line for line in plane.lines}
            mods = _modules(lines["XLA Modules"]) if "XLA Modules" in lines \
                else []
            rows = ops.setdefault(dev.group(2), [])
            for name in _OPS_LINES:
                if name not in lines:
                    continue
                m = 0
                for ev in lines[name].events:
                    start = int(ev.start_ns)
                    while m < len(mods) and mods[m][1] < start:
                        m += 1
                    prog = mods[m][2] if m < len(mods) and \
                        mods[m][0] <= start else ""
                    op = _op_name(ev.name)
                    rows.append([op, start, int(ev.duration_ns), prog,
                                 scopes.get(prog, {}).get(op, "")])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return Events(ops=ops, host=host)


def _union(intervals: Iterable) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclasses.dataclass
class Summary:
    window_ns: int
    busy_ns: float                # union of op intervals, mean over chips
    op_ns: dict                   # op label -> summed duration (all chips)
    scope_ops: list               # [(scope, program, dur_ns)] per op event
    # (op_ns and scope_ops leave out control flow, whose events span the
    # events of the operations in their bodies)
    gaps: list                    # [(dur_ns, host activity)] longest first
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def scope_ns(self, *patterns: str) -> float:
        """Summed duration, mean over chips, of the operations whose name
        scope has a path component that starts with one of ``patterns``,
        under any transformation's wrapper (``transpose(jvp(<scope>))``
        is the backward of ``<scope>``)."""
        tot = 0
        for scope, _, dur in self.scope_ops:
            parts = [_WRAPPER.sub("", p) for p in scope.split("/")]
            if any(p.startswith(pat) for p in parts for pat in patterns):
                tot += dur
        return tot / self.chips

    def program_ns(self, *substrings: str) -> float:
        """Summed duration, mean over chips, of the operations of programs
        whose name contains one of ``substrings``."""
        tot = sum(d for _, prog, d in self.scope_ops
                  if any(s in prog for s in substrings))
        return tot / self.chips


def reduce(ev: Events, max_gaps: int = 10) -> Summary:
    """Reduce to the traced window: the one ``bench:window`` host span."""
    win = [h for h in ev.host if h[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, got {len(win)}")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    spans = sorted((h for h in ev.host if h[0] != WINDOW_SPAN),
                   key=lambda h: h[1])
    busy, op_ns, scope_ops, gaps = [], {}, [], []
    labels: dict = {}
    chips = max(len(ev.ops), 1)
    for rows in ev.ops.values():
        ivs = []
        for name, start, dur, prog, scope in rows:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            if _CONTROL.match(name):
                continue
            key = f"{prog}:{name}"
            op_ns[key] = op_ns.get(key, 0) + (b - a)
            labels.setdefault(key, _label(prog, name, scope))
            scope_ops.append((scope, prog, b - a))
        merged = _union(ivs)
        busy.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, _host_at(spans, (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])
    return Summary(window_ns=w1 - w0, busy_ns=sum(busy) / chips,
                   op_ns={labels[k]: v for k, v in op_ns.items()},
                   scope_ops=scope_ops, gaps=gaps[:max_gaps], chips=chips)


_NOISE = re.compile(r"^(jit\(.*\)|while|body|closed_call|checkpoint|"
                    r"rematted_computation|jvp\(\)|transpose\(jvp\(\)\))$")


def _label(prog: str, op: str, scope: str) -> str:
    """``program:op [scope]``, the scope without its control-flow parts."""
    parts = [p for p in scope.split("/") if p and not _NOISE.match(p)]
    tail = "/".join(parts)[-100:]
    return f"{prog}:{op} [{tail}]" if tail else f"{prog}:{op}"


def _host_at(spans: list, t: int) -> str:
    """Innermost benchmark host span open at time ``t``."""
    best = None
    for name, start, dur in spans:
        if start > t:
            break
        if start + dur >= t and (best is None or start >= best[1]):
            best = (name, start)
    return best[0][len(HOST_PREFIX):] if best else "none"


def breakdown(s: Summary, top: int = 10) -> dict:
    ops = sorted(s.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, v / s.chips / 1e9] for n, v in ops],
            "idle_gaps": [[h, d / 1e9] for d, h in s.gaps[:top]]}
