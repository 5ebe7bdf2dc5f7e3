"""Reduce a profiler trace (``.xplane.pb``) of a steady window to what the
per-layer metric readers (``metrics/*.py``) read.

The harness marks every timed round on the host with a
``chipbench.round`` annotation, and the calls it makes into the program's
host layers with ``chipbench.<layer>`` ones. The traced window runs from
the first round's start to the last round's end. Device time comes from the
TPU planes (``/device:TPU:<n>``): their ``XLA Modules`` line holds one event
per executed jit program (``jit_<function>(<fingerprint>)``), their ``XLA
Ops`` line one per operation (named by ``op_name``); an operation belongs to
the module whose event contains its start.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

ROUND_SPAN = "chipbench.round"
SPAN_PREFIX = "chipbench."
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns, on the trace's common clock
    end: float


def _events(line) -> list:
    return [Event(e.name, e.start_ns, e.end_ns) for e in line.events]


def op_name(hlo: str) -> str:
    """An operation's name from its event name, which on TPU is the HLO
    text (``%stream_scatter_add.1 = f32[...] custom-call(...)``): the
    instruction's name without its ``.N`` suffix. A Pallas kernel's custom
    call is named after the kernel's function."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def _ops(line) -> list:
    return [Event(op_name(e.name), e.start_ns, e.end_ns)
            for e in line.events]


@dataclasses.dataclass
class Device:
    """One TPU core's events."""

    name: str
    modules: list
    ops: list

    def module_of(self, ev: Event) -> str:
        if not hasattr(self, "_starts"):
            self._starts = [m.start for m in self.modules]
        i = bisect.bisect_right(self._starts, ev.start) - 1
        if i >= 0 and self.modules[i].end >= ev.start:
            return self.modules[i].name
        return ""


class View:
    """A traced window, with the facts the readers need beside it.

    ``facts`` holds, for the traced rounds: ``rounds`` (the accounting
    facts of each, as in ``accounting.upload_vs_dense``), ``peaks``
    (``peaks.PEAKS`` entry), ``train_flops_per_round`` and ``kernels``
    (kernel -> the operation names it has in the trace).
    """

    def __init__(self, profile, facts: dict):
        self.facts = facts
        self.devices, spans = [], []
        for plane in profile.planes:
            lines = {ln.name: ln for ln in plane.lines}
            if plane.name.startswith(DEVICE_PREFIX):
                self.devices.append(Device(
                    plane.name,
                    sorted(_events(lines["XLA Modules"]),
                           key=lambda e: e.start)
                    if "XLA Modules" in lines else [],
                    sorted(_ops(lines["XLA Ops"]), key=lambda e: e.start)
                    if "XLA Ops" in lines else []))
            else:
                for ln in plane.lines:
                    spans += [e for e in _events(ln)
                              if e.name.startswith(SPAN_PREFIX)]
        self.spans = sorted(spans, key=lambda e: e.start)
        rounds = [s for s in self.spans if s.name == ROUND_SPAN]
        self.n_rounds = len(rounds)
        self.start = min((s.start for s in rounds), default=0.0)
        self.end = max((s.end for s in rounds), default=0.0)

    # ------------------------------------------------------------ window
    @property
    def window_s(self) -> float:
        return (self.end - self.start) * 1e-9

    def _clip(self, events) -> list:
        return [(max(e.start, self.start), min(e.end, self.end), e)
                for e in events if e.end > self.start and e.start < self.end]

    def busy_intervals(self, dev: Device) -> list:
        """Union of the device's operation intervals inside the window."""
        out = []
        for s, e, _ in sorted(self._clip(dev.ops), key=lambda t: t[0]):
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return out

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in self.devices) * 1e-9 / len(self.devices)

    # ------------------------------------------------------------ layers
    def module_s(self, pattern: str) -> float:
        """Device seconds, averaged over chips, of the modules whose name
        holds ``pattern``, inside the window."""
        if not self.devices:
            return 0.0
        return sum(e - s for d in self.devices
                   for s, e, m in self._clip(d.modules)
                   if pattern in m.name) * 1e-9 / len(self.devices)

    def ops_named(self, names) -> list:
        """The window's operations whose name is in ``names``."""
        names = set(names)
        return [(s, e, op) for d in self.devices
                for s, e, op in self._clip(d.ops) if op.name in names]

    # --------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations (by module and name) that took most device
        time in the window: ``[[name, seconds], ...]``."""
        tot = collections.Counter()
        for d in self.devices:
            for s, e, op in self._clip(d.ops):
                mod = d.module_of(op).split("(")[0]
                tot[f"{mod}/{op.name}"] += (e - s) * 1e-9
        return [[k, v / max(len(self.devices), 1)]
                for k, v in tot.most_common(n)]

    def idle_by_host(self, n: int = 10) -> list:
        """Device idle time in the window, by what the host was doing:
        the innermost ``chipbench.*`` span (other than the round) that
        covers the middle of each gap, else ``round: other host work``.
        ``[[activity, seconds], ...]``, longest first."""
        inner = [s for s in self.spans if s.name != ROUND_SPAN]
        starts = [s.start for s in inner]
        tot = collections.Counter()
        for d in self.devices:
            prev = self.start
            for s, e in self.busy_intervals(d) + [[self.end, self.end]]:
                if s > prev:
                    mid = (prev + s) / 2
                    what = "round: other host work"
                    i = bisect.bisect_right(starts, mid) - 1
                    # host spans are short and rarely nested: look back a few
                    for i in range(i, max(i - 8, -1), -1):
                        if inner[i].end >= mid:
                            what = inner[i].name[len(SPAN_PREFIX):]
                            break
                    tot[what] += (s - prev) * 1e-9
                prev = max(prev, e)
        return [[k, v / max(len(self.devices), 1)]
                for k, v in tot.most_common(n)]
