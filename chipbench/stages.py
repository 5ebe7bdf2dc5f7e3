"""Device idle time by the program stage that held the chip back.

The program writes a host span (``fl.*``, with its stats) at every stage
boundary of a round, nested under ``fl.round``. ``program_spans`` takes
them from a profile; ``trace.View`` keeps only the harness's own
``chipbench.*`` spans, so the caller passes both. The profiler stamps host
and device events on one clock, so each device idle interval inside the
traced window is cut at the spans' boundaries, and each piece goes to the
innermost span open on the host at that instant, or to no span. On a v5e the
two agree to within about 1.3 ms (a device program can appear that much before
the host call that dispatched it), so a piece that near a stage boundary may
go to the neighbouring stage. ``LAYERS`` sums the stages of each host layer
(``idle_ms``); idle time under ``fl.engine.hooks``, under the self time of
``fl.round`` or outside every span goes to none of them.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

from chipbench.trace import DEVICE_PREFIX, Event

PROGRAM_PREFIX = "fl."
SIM_ENGINE = frozenset({"fl.engine.sample", "fl.engine.batches",
                        "fl.engine.record"})
SECAGG_PREFIX = "fl.secagg."
RUN_ROUND = frozenset({
    "fl.local_sgd", "fl.host_read", "fl.schedule", "fl.restack",
    "fl.encode", "fl.decode", "fl.encode_decode", "fl.residuals",
    "fl.server_update"})
# idle milliseconds a round under each host layer's stages
LAYERS = {
    "idle_engine_ms": SIM_ENGINE.__contains__,
    "idle_secagg_ms": lambda name: name.startswith(SECAGG_PREFIX),
    "idle_run_round_ms": RUN_ROUND.__contains__,
}


@dataclasses.dataclass(frozen=True)
class Span(Event):
    """A span the program writes (``fl.*``), with its stats."""

    stats: dict = dataclasses.field(default_factory=dict)


def program_spans(profile) -> list:
    """Every host event of ``profile`` whose name starts with ``fl.``, with
    its stats, sorted by ``(start, -end)``: outermost first where two start
    together."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for ln in plane.lines:
            out += [Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in ln.events if e.name.startswith(PROGRAM_PREFIX)]
    return sorted(out, key=lambda e: (e.start, -e.end))


def innermost(spans) -> tuple:
    """``(cuts, owner)``: the sorted span boundaries and, for each interval
    ``[cuts[i], cuts[i + 1])``, the name of the innermost span that covers
    it (the latest to start), or None. ``spans`` are sorted as
    ``program_spans`` sorts them."""
    starts = [s.start for s in spans]
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    owner = []
    for a, b in zip(cuts, cuts[1:]):
        name = None
        for i in range(bisect.bisect_right(starts, a) - 1, -1, -1):
            if spans[i].end >= b:
                name = spans[i].name
                break
        owner.append(name)
    return cuts, owner


def idle_by_stage(view, spans) -> dict | None:
    """Device idle seconds in ``view``'s window by innermost program span
    (None for idle time outside every span), averaged over the chips; None
    when there is no program span or no device."""
    if not spans or not view.devices or view.window_s <= 0:
        return None
    cuts, owner = innermost(spans)
    tot = collections.Counter()
    for d in view.devices:
        prev = view.start
        for s, e in view.busy_intervals(d) + [[view.end, view.end]]:
            if s > prev:
                _assign(prev, s, cuts, owner, tot)
            prev = max(prev, e)
    return {k: v * 1e-9 / len(view.devices) for k, v in tot.items()}


def _assign(lo: float, hi: float, cuts: list, owner: list, tot) -> None:
    """Add the idle interval ``[lo, hi)`` to ``tot``, piece by piece."""
    k = bisect.bisect_right(cuts, lo) - 1
    while lo < hi:
        end = min(hi, cuts[k + 1]) if k + 1 < len(cuts) else hi
        tot[owner[k] if 0 <= k < len(owner) else None] += end - lo
        lo, k = end, k + 1


def idle_ms(view, spans) -> dict | None:
    """Device idle milliseconds per round under each of ``LAYERS``; None
    when there is no program span."""
    by_stage = idle_by_stage(view, spans)
    if by_stage is None or view.n_rounds == 0:
        return None
    return {layer: 1e3 * sum(v for name, v in by_stage.items()
                             if name is not None and in_layer(name))
            / view.n_rounds for layer, in_layer in LAYERS.items()}
