"""Step flight recorder: per-step stage attribution on one monotonic clock.

Every engine step emits one fixed-shape record — batch lineage id, tenant
mix, event count, and a segment timeline attributing wall time to the
stages of the step path (pack / route / guard / H2D / dispatch /
device-compute / lane-fetch / materialize).  Records are stitched across
the feeder, submitter, and caller threads by *carrying the record object*
through the hand-off structures (`_PreparedStep.flight`, the pipelined
submitter's ready-heap tuples) instead of relying on thread-local span
stacks, which lose parentage at every thread hop.

Hot-path cost is pinned by perf_gate's ``observability_overhead`` check:
recording is lock-free — slots are preallocated, claimed with an atomic
``itertools.count`` ticket, and a mark is two list stores of a
``perf_counter()`` float.  No allocation, dict lookup by string hash only,
and no string formatting until export.

All timestamps share ``time.perf_counter()`` so segments from different
threads are directly comparable: that is what makes
``h2d_overlap_fraction`` (how much of this step's staging-side work ran
while the previous step's dispatch was in flight) computable at export
time without any runtime coordination.

The same recorder, given another stage vocabulary, keeps one record per
bus consumer cycle (:class:`CycleRecorder`, ``GLOBAL_CYCLES``): the
cycle's poll, handler and commit, and inside the inbound handler the
stages each record passes through, accumulated over the cycle's records.
Leaf stages also open a ``jax.profiler.TraceAnnotation`` while a profiler
trace is being recorded, so the device trace names what the host was
doing on its own clock.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

# Stage vocabulary — fixed order, fixed index per stage.  The index is
# resolved once at module import; the hot path indexes preallocated
# lists, never touching a dict keyed by a freshly built string.
STAGES: Tuple[str, ...] = (
    "pack",            # host: batch -> wire blob (batch_to_blob)
    "route_host",      # sharded host fallback: arena router route_batch
    "route_device",    # sharded device path: flat-blob pack for radix route
    "guard",           # host: wait on staging-ring transfer guard
    "stage_wait",      # host: backpressure wait for a free staging-ring slot
    "h2d",             # host: device_put submit (async; segment = submit cost)
    "dispatch",        # host: jit step call until handles returned
    "device_compute",  # device: dispatch start -> outputs ready (needs sync)
    "model_eval",      # host: resolve anomaly-model fires from fetched lanes
    "lane_fetch",      # host: the one device_get of the alert+command lanes
    "materialize",     # host: decode lanes + emit alert events
    "actuate",         # host: decode command lanes + resolve policy fires
    "command_fanout",  # host: dispatch resolved commands to destinations
)
# Engine host stages that also write a profiler span, each on the thread
# that runs it.
STEP_SPANS: Dict[str, str] = {
    "pack": "step.pack", "h2d": "step.h2d", "dispatch": "step.dispatch",
    "lane_fetch": "step.lane_fetch", "materialize": "step.materialize",
}

# Consumer-cycle vocabulary.  The bus boundary's stages come first (every
# consumer group records them); the rest are the inbound handler's, each
# accumulated over the cycle's records.  `handler` and `persist` are
# parents: their children account for their time.
CYCLE_STAGES: Tuple[str, ...] = (
    "poll",             # consumer.poll, including its wait
    "handler",          # the group's handler over the polled batch
    "commit",           # bus.commit of the group's offsets
    "decode",           # inbound: msgpack decode + event objects
    "validate",         # inbound: device + active-assignment lookups
    "persist",          # inbound: parent of the three persist.* stages
    "persist.context",  # persist: device/assignment lookups, context, stamp
    "persist.append",   # persist: the columnar log append
    "persist.fanout",   # persist: the triggers' publish of every event
    "pack_events",      # inbound: the packer generator's own time
    "step",             # inbound: engine.submit_routed
    "materialize",      # inbound: engine.materialize_alerts
    "alert_persist",    # inbound: rule-alert persist + drain_parked
)
# The stages every consumer group records: the groups whose handler marks
# nothing of its own keep records of these alone.
BUS_STAGES: Tuple[str, ...] = CYCLE_STAGES[:3]
# Profiler span of each inbound leaf.  Parents open none, or
# tracereduce.attribute would name every idle gap after the parent; the
# bus stages are named per consumer by its ConsumerHost.
CYCLE_SPANS: Dict[str, str] = {
    "decode": "inbound.decode", "validate": "inbound.validate",
    "persist.context": "persist.context", "persist.append": "persist.append",
    "persist.fanout": "persist.fanout", "pack_events": "inbound.pack_events",
    "step": "inbound.step", "materialize": "inbound.materialize",
    "alert_persist": "inbound.alert_persist",
}

_INDEXES: Dict[Tuple[str, ...], Dict[str, int]] = {}


def _index_of(stages: Tuple[str, ...]) -> Dict[str, int]:
    """Stage name -> slot index, one shared dict per vocabulary."""
    index = _INDEXES.get(stages)
    if index is None:
        index = _INDEXES.setdefault(
            stages, {name: i for i, name in enumerate(stages)})
    return index


_TraceAnnotation = None


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named `name` (imported on first
    use, so that importing this module does not import JAX)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation
        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(name)


def trace_enabled() -> bool:
    """True while a profiler trace is being recorded."""
    if _TraceAnnotation is None:
        annotation("")
    return _TraceAnnotation.is_enabled()


def _enter(name: str):
    span = annotation(name)
    span.__enter__()
    return span


# Staging-side stages: work that a feeder thread can run ahead while the
# step thread still has the previous step's dispatch in flight.  Overlap
# of these segments with the preceding record's dispatch window is the
# ``h2d_overlap_fraction`` ROADMAP item 2 will be gated on.
# ``stage_wait`` is deliberately NOT here: time spent blocked on a full
# staging ring is backpressure, not productive staging work — counting it
# would inflate the overlap fraction exactly when the ring stalls.
_STAGING_STAGES = ("pack", "route_host", "route_device", "guard", "h2d")


class StepRecord:
    """One preallocated flight-record slot.

    ``begin``/``end`` are fixed-length float lists indexed by stage; a
    negative value means "not recorded".  ``reset`` re-arms the slot for
    reuse without reallocating.  `stages` is the record's vocabulary and
    `spans` names the stages that also write a profiler span.
    """

    __slots__ = ("seq", "gen", "engine", "events", "tenant_mix",
                 "begin", "end", "created", "age", "ring", "commands",
                 "stages", "_index", "_spans", "_open", "_unset")

    def __init__(self, stages: Tuple[str, ...] = STAGES,
                 spans: Optional[Dict[str, str]] = None) -> None:
        self.stages = stages
        self._index = _index_of(stages)
        spans = STEP_SPANS if spans is None else spans
        self._spans = tuple(spans.get(name) for name in stages)
        self._open: List = [None] * len(stages)  # entered annotations
        self._unset = (-1.0,) * len(stages)
        self.seq = -1            # lineage id (recorder-wide monotonic)
        self.gen = -1            # ring generation (claim ticket)
        self.engine = ""         # engine scope name; a cycle's consumer
        self.events = 0
        self.tenant_mix: Optional[Tuple[int, ...]] = None
        self.begin: List[float] = [-1.0] * len(stages)
        self.end: List[float] = [-1.0] * len(stages)
        self.created = 0.0
        # event-age ride-along (runtime/eventage.py): an open AgeSidecar
        # while the batch is in flight, replaced by the closed AgeSummary
        # at materialize — export only reads the closed form
        self.age = None
        # staging-ring snapshot at slot-acquire time: (occupancy, depth),
        # None when the step never touched the ring
        self.ring: Optional[Tuple[int, int]] = None
        # command fires resolved from this step's command lane (actuate
        # stage); drives the detection_to_actuation age edge
        self.commands = 0

    # -- hot path -----------------------------------------------------
    def reset(self, seq: int, gen: int, engine: str) -> None:
        self.seq = seq
        self.gen = gen
        self.engine = engine
        self.events = 0
        self.tenant_mix = None
        self.begin[:] = self._unset
        self.end[:] = self._unset
        self.created = time.perf_counter()
        self.age = None
        self.ring = None
        self.commands = 0

    def mark(self, stage: str, t0: float, t1: float) -> None:
        """Record a completed segment from explicit timestamps."""
        i = self._index[stage]
        self.begin[i] = t0
        self.end[i] = t1

    def begin_stage(self, stage: str) -> None:
        i = self._index[stage]
        span = self._spans[i]
        self._open[i] = (_enter(span) if span is not None
                         and trace_enabled() else None)
        self.begin[i] = time.perf_counter()

    def end_stage(self, stage: str) -> None:
        i = self._index[stage]
        self.end[i] = time.perf_counter()
        span = self._open[i]
        if span is not None:
            self._open[i] = None
            span.__exit__(None, None, None)

    # -- cold path (export / tests) -----------------------------------
    def _duration(self, i: int) -> float:
        return max(0.0, self.end[i] - self.begin[i])

    def stage_s(self, stage: str) -> float:
        """Duration of one stage in seconds, 0.0 if unrecorded."""
        i = self._index[stage]
        if self.begin[i] < 0.0 or self.end[i] < 0.0:
            return 0.0
        return self._duration(i)

    def copy(self) -> "StepRecord":
        """A detached copy for export (the slot may be re-armed after)."""
        copy = type(self)(self.stages)
        copy.seq = self.seq
        copy.gen = self.gen
        copy.engine = self.engine
        copy.events = self.events
        copy.tenant_mix = self.tenant_mix
        copy.begin = list(self.begin)
        copy.end = list(self.end)
        copy.created = self.created
        copy.age = self.age
        copy.ring = self.ring
        return copy

    def span_bounds(self) -> Optional[Tuple[float, float]]:
        """(first begin, last end) across recorded segments."""
        first = None
        last = None
        for i in range(len(self.stages)):
            if self.begin[i] >= 0.0 and self.end[i] >= 0.0:
                first = self.begin[i] if first is None else min(
                    first, self.begin[i])
                last = self.end[i] if last is None else max(
                    last, self.end[i])
        if first is None or last is None:
            return None
        return first, last

    def export(self) -> Dict:
        """Dict form for the REST endpoint / bench.  Allocates — never
        called from the hot path."""
        stages = {}
        sum_s = 0.0
        crit = ""
        crit_s = -1.0
        for i, name in enumerate(self.stages):
            if self.begin[i] < 0.0 or self.end[i] < 0.0:
                continue
            dur = self._duration(i)
            stages[name] = {
                "begin_s": self.begin[i],
                "ms": round(dur * 1e3, 6),
            }
            sum_s += dur
            if dur > crit_s:
                crit_s = dur
                crit = name
        bounds = self.span_bounds()
        span_s = (bounds[1] - bounds[0]) if bounds else 0.0
        out = {
            "seq": self.seq,
            "engine": self.engine,
            "events": self.events,
            "stages": stages,
            "sum_ms": round(sum_s * 1e3, 6),
            "span_ms": round(span_s * 1e3, 6),
            "critical_stage": crit,
        }
        if self.tenant_mix is not None:
            out["tenant_mix"] = list(self.tenant_mix)
        if self.ring is not None:
            out["ring"] = {"occupancy": self.ring[0],
                           "depth": self.ring[1]}
        age = self.age
        if age is not None and hasattr(age, "export"):
            exported = age.export()
            if exported.get("count"):
                out["age"] = exported
        return out


class CycleRecord(StepRecord):
    """One consumer cycle: a poll that returned records, the handler over
    them and the commit.

    A stage may repeat within the cycle, once per record: ``open`` and
    ``close`` accumulate it, keeping the first begin, the last end, the
    summed duration (``acc``) and the count (``n``).  The handler is
    handed the record explicitly; nothing rides a thread-local.
    ``traced`` is read once per cycle, when the cycle is claimed."""

    __slots__ = ("records", "cpu_s", "error", "steps", "traced", "acc", "n",
                 "_t0", "_blank")

    def __init__(self, stages: Tuple[str, ...] = CYCLE_STAGES) -> None:
        super().__init__(stages, CYCLE_SPANS)
        self.acc: List[float] = [0.0] * len(stages)
        self.n: List[int] = [0] * len(stages)
        self._t0: List[float] = [0.0] * len(stages)
        self._blank = ((0.0,) * len(stages), (0,) * len(stages),
                       (None,) * len(stages))
        self.records = 0
        self.cpu_s = 0.0      # consumer thread CPU over handler + commit
        self.error: Optional[str] = None
        self.steps: List[int] = []   # seq of each step record it caused
        self.traced = False

    # -- hot path -----------------------------------------------------
    def reset(self, seq: int, gen: int, engine: str) -> None:
        super().reset(seq, gen, engine)
        self.acc[:], self.n[:], self._open[:] = self._blank
        self.records = 0
        self.cpu_s = 0.0
        self.error = None
        self.steps = []
        self.traced = False

    def open(self, stage: str) -> None:
        i = self._index[stage]
        if self.traced:
            span = self._spans[i]
            self._open[i] = _enter(span) if span is not None else None
        self._t0[i] = time.perf_counter()

    def close(self, stage: str) -> None:
        t1 = time.perf_counter()
        i = self._index[stage]
        t0 = self._t0[i]
        if self.n[i] == 0:
            self.begin[i] = t0
        self.end[i] = t1
        self.acc[i] += t1 - t0
        self.n[i] += 1
        span = self._open[i]
        if span is not None:
            self._open[i] = None
            span.__exit__(None, None, None)

    def caused(self, step: Optional[StepRecord]) -> None:
        """Link the step record this cycle's work dispatched."""
        if step is not None:
            self.steps.append(step.seq)

    # -- cold path ----------------------------------------------------
    def _duration(self, i: int) -> float:
        if self.n[i]:
            return self.acc[i]
        return max(0.0, self.end[i] - self.begin[i])

    def copy(self) -> "CycleRecord":
        copy = super().copy()
        copy.acc = list(self.acc)
        copy.n = list(self.n)
        copy.records = self.records
        copy.cpu_s = self.cpu_s
        copy.error = self.error
        copy.steps = list(self.steps)
        return copy

    def export(self) -> Dict:
        stages = {}
        for i, name in enumerate(self.stages):
            if self.begin[i] < 0.0 or self.end[i] < 0.0:
                continue
            entry = {"begin_s": self.begin[i],
                     "ms": round(self._duration(i) * 1e3, 6)}
            if self.n[i]:
                entry["n"] = self.n[i]
            stages[name] = entry
        bounds = self.span_bounds()
        out = {
            "seq": self.seq,
            "consumer": self.engine,
            "records": self.records,
            "events": self.events,
            "stages": stages,
            "span_ms": round((bounds[1] - bounds[0]) * 1e3, 6)
            if bounds else 0.0,
            "cpu_ms": round(self.cpu_s * 1e3, 6),
            "steps": list(self.steps),
        }
        if self.error is not None:
            out["error"] = self.error
        return out


class _NoCycle:
    """Stands in for a cycle record where the caller carries none: every
    mark is dropped."""

    __slots__ = ()

    def open(self, stage: str) -> None:
        pass

    def close(self, stage: str) -> None:
        pass

    def caused(self, step) -> None:
        pass


NO_CYCLE = _NoCycle()


class FlightRecorder:
    """Fixed-capacity ring of preallocated :class:`StepRecord` slots.

    ``begin_step`` claims the next slot with an atomic counter ticket
    (``itertools.count`` advances under the GIL without a lock) and
    re-arms it; concurrent writers from feeder/submitter/caller threads
    each hold a distinct slot, so marks never contend.  Export walks the
    ring snapshot-style, tolerating slots being rewritten mid-walk by
    checking the generation ticket before and after the copy.
    """

    def __init__(self, capacity: int = 256,
                 stages: Tuple[str, ...] = STAGES,
                 record_type: type = StepRecord) -> None:
        self.capacity = int(capacity)
        self.stages = stages
        self._slots = [record_type(stages) for _ in range(self.capacity)]
        self._ticket = itertools.count()
        self._export_lock = threading.Lock()

    # -- hot path -----------------------------------------------------
    def begin_step(self, engine: str = "") -> StepRecord:
        gen = next(self._ticket)
        rec = self._slots[gen % self.capacity]
        rec.reset(seq=gen, gen=gen, engine=engine)
        return rec

    # -- cold path ----------------------------------------------------
    def stable_records(self, last_n: int) -> List[StepRecord]:
        """Detached copies of the most recent slots, newest last."""
        with self._export_lock:
            return self._stable_records(last_n)

    def _stable_records(self, last_n: int) -> List[StepRecord]:
        """Copy out the most recent completed slots, newest last.

        A slot is taken only if its generation ticket is unchanged
        across the copy (it wasn't re-armed mid-read)."""
        # itertools.count cannot be peeked without advancing; take the
        # high-water mark from the slots themselves instead.
        top = max((s.gen for s in self._slots), default=-1)
        out: List[StepRecord] = []
        lo = max(0, top - min(last_n, self.capacity) + 1)
        for gen in range(lo, top + 1):
            slot = self._slots[gen % self.capacity]
            if slot.gen != gen:
                continue
            copy = slot.copy()
            if slot.gen != gen:  # re-armed while we copied: discard
                continue
            out.append(copy)
        return out

    def export(self, last_n: int = 64) -> Dict:
        """Records + rollups for ``GET /api/instance/flight``."""
        recs = self.stable_records(last_n)
        records = [r.export() for r in recs]
        return {
            "capacity": self.capacity,
            "count": len(records),
            "stages": list(self.stages),
            "records": records,
            "rollups": self._rollups(recs),
        }

    def _rollups(self, recs: Sequence[StepRecord]) -> Dict:
        """Window aggregates: per-stage occupancy, sum-vs-max decomposed
        sync time, h2d overlap fraction, critical-path histogram."""
        if not recs:
            return {"steps": 0}
        window_lo = None
        window_hi = None
        stages = self.stages
        stage_tot = [0.0] * len(stages)
        sum_ms: List[float] = []
        max_ms: List[float] = []
        crit_count: Dict[str, int] = {}
        events = 0
        for r in recs:
            bounds = r.span_bounds()
            if bounds is None:
                continue
            window_lo = bounds[0] if window_lo is None else min(
                window_lo, bounds[0])
            window_hi = bounds[1] if window_hi is None else max(
                window_hi, bounds[1])
            rec_sum = 0.0
            rec_max = 0.0
            crit = ""
            for i in range(len(stages)):
                if r.begin[i] < 0.0 or r.end[i] < 0.0:
                    continue
                dur = r._duration(i)
                stage_tot[i] += dur
                rec_sum += dur
                if dur > rec_max:
                    rec_max = dur
                    crit = stages[i]
            sum_ms.append(rec_sum * 1e3)
            max_ms.append(rec_max * 1e3)
            if crit:
                crit_count[crit] = crit_count.get(crit, 0) + 1
            events += r.events
        if window_lo is None or window_hi is None:
            return {"steps": 0}
        wall = max(window_hi - window_lo, 1e-9)
        occupancy = {
            stages[i]: round(stage_tot[i] / wall, 4)
            for i in range(len(stages)) if stage_tot[i] > 0.0
        }
        n = len(sum_ms)
        # ingest->effect event-age rollup: merge the closed AgeSummary
        # ride-alongs across the window and derive p50/p99 from the log2
        # buckets (runtime/eventage.py) — the flight endpoint's answer to
        # "how old were events when their effects landed"
        age_total = None
        for r in recs:
            age = r.age
            if age is None or not hasattr(age, "buckets") \
                    or not getattr(age, "count", 0):
                continue
            if age_total is None:
                from sitewhere_tpu.runtime.eventage import AgeSummary
                age_total = AgeSummary()
            age_total.merge(age)
        out_age = age_total.export() if age_total is not None else None
        # staging-ring occupancy rollup: how full the H2D ring ran across
        # the window (mean/max of the at-acquire snapshots).  A ring
        # pinned at depth means the feeder is transfer-bound; zero means
        # the ring never engaged (serial path or depth 1 idle).
        ring_occ = [r.ring[0] for r in recs if r.ring is not None]
        ring_depth = max((r.ring[1] for r in recs if r.ring is not None),
                         default=0)
        ring_out = None
        if ring_occ:
            ring_out = {
                "depth": ring_depth,
                "mean_occupancy": round(sum(ring_occ) / len(ring_occ), 3),
                "max_occupancy": max(ring_occ),
            }
        return {
            "steps": n,
            "events": events,
            "window_ms": round(wall * 1e3, 3),
            **({"event_age": out_age} if out_age else {}),
            **({"staging_ring": ring_out} if ring_out else {}),
            "stage_occupancy": occupancy,
            # sum-vs-max: if the pipeline overlapped perfectly, wall per
            # step converges to the max stage cost; serial execution
            # pays the sum.  Both are exported so the ratio is readable.
            "sync_total_ms": {
                "sum_of_stages": round(sum(sum_ms) / n, 4),
                "max_stage": round(sum(max_ms) / n, 4),
            },
            "critical_stage_counts": crit_count,
            "h2d_overlap_fraction": round(
                self._h2d_overlap_fraction(recs), 4),
        }

    def _h2d_overlap_fraction(self, recs: Sequence[StepRecord]) -> float:
        """Fraction of staging-side work (pack/route/guard/h2d) that ran
        while the *previous* record's dispatch window was still open.

        Zero for a serial submit loop; approaches 1.0 when a feeder
        stages batch N+1 entirely under batch N's dispatch.  Computable
        offline because every mark shares one monotonic clock."""
        index = _index_of(self.stages)
        if "dispatch" not in index:
            return 0.0
        di = index["dispatch"]
        staging_idx = [index[s] for s in _STAGING_STAGES if s in index]
        total = 0.0
        overlapped = 0.0
        by_seq = sorted(recs, key=lambda r: r.seq)
        for prev, cur in zip(by_seq, by_seq[1:]):
            if prev.begin[di] < 0.0 or prev.end[di] < 0.0:
                continue
            d0, d1 = prev.begin[di], prev.end[di]
            for i in staging_idx:
                if cur.begin[i] < 0.0 or cur.end[i] < 0.0:
                    continue
                b, e = cur.begin[i], cur.end[i]
                total += max(0.0, e - b)
                overlapped += max(0.0, min(e, d1) - max(b, d0))
        if total <= 0.0:
            return 0.0
        return min(1.0, overlapped / total)


class CycleRecorder:
    """Consumer cycles (:class:`CycleRecord`): one claimed per poll that
    returned records (empty polls claim none), in a ring of `capacity`
    slots per consumer label, so that a fast-cycling group never evicts a
    slow one's history.  ``seq`` is recorder-wide.  A ring may still wrap
    on a fast group: the lossless view is the ``bus.consumer_*``
    histograms each cycle is folded into when it closes."""

    def __init__(self, capacity: int = 128) -> None:
        self.capacity = int(capacity)
        self._rings: Dict[str, FlightRecorder] = {}
        self._lock = threading.Lock()
        self._seq = itertools.count()

    # -- hot path -----------------------------------------------------
    def begin_cycle(self, consumer: str, traced: bool,
                    stages: Tuple[str, ...] = CYCLE_STAGES) -> CycleRecord:
        """Claim `consumer`'s next slot; its ring's records hold `stages`
        (fixed by the consumer's first claim)."""
        ring = self._rings.get(consumer)
        if ring is None:
            with self._lock:
                ring = self._rings.get(consumer)
                if ring is None:
                    ring = self._rings[consumer] = FlightRecorder(
                        self.capacity, stages, CycleRecord)
        rec = ring.begin_step(consumer)
        rec.seq = next(self._seq)
        rec.traced = traced
        return rec

    # -- cold path ----------------------------------------------------
    def export(self, last_n: int = 64,
               consumer: Optional[str] = None) -> Dict:
        """The last `last_n` cycles (of `consumer` alone, where given),
        oldest first, and per-consumer rollups over them."""
        with self._lock:
            rings = dict(self._rings)
        if consumer is not None:
            rings = {consumer: rings[consumer]} if consumer in rings else {}
        recs: List[CycleRecord] = []
        for ring in rings.values():
            recs.extend(ring.stable_records(last_n))
        recs.sort(key=lambda r: r.seq)
        recs = recs[-last_n:] if last_n > 0 else []
        return {
            "capacity": self.capacity,
            "count": len(recs),
            "stages": list(CYCLE_STAGES),
            "records": [r.export() for r in recs],
            "rollups": self._rollups(recs),
        }

    def _rollups(self, recs: Sequence[CycleRecord]) -> Dict:
        """Per consumer: cycles, records, events, errors, wall (poll start
        to last mark), thread CPU and summed time per stage."""
        out: Dict[str, Dict] = {}
        for r in recs:
            c = out.get(r.engine)
            if c is None:
                c = out[r.engine] = {"cycles": 0, "records": 0, "events": 0,
                                     "errors": 0, "wall_ms": 0.0,
                                     "cpu_ms": 0.0, "stage_ms": {}}
            c["cycles"] += 1
            c["records"] += r.records
            c["events"] += r.events
            c["errors"] += r.error is not None
            bounds = r.span_bounds()
            if bounds is not None:
                c["wall_ms"] += (bounds[1] - bounds[0]) * 1e3
            c["cpu_ms"] += r.cpu_s * 1e3
            for i, name in enumerate(r.stages):
                if r.begin[i] >= 0.0 and r.end[i] >= 0.0:
                    c["stage_ms"][name] = (c["stage_ms"].get(name, 0.0)
                                           + r._duration(i) * 1e3)
        for c in out.values():
            c["wall_ms"] = round(c["wall_ms"], 3)
            c["cpu_ms"] = round(c["cpu_ms"], 3)
            c["stage_ms"] = {k: round(v, 3) for k, v in c["stage_ms"].items()}
        return {"cycles": len(recs), "by_consumer": out}


# Process-wide recorders: engines default to GLOBAL_FLIGHT, every bus
# ConsumerHost records into GLOBAL_CYCLES; the REST endpoint and the
# benchmark read both.  Mirrors GLOBAL_METRICS / GLOBAL_TRACER.
GLOBAL_FLIGHT = FlightRecorder()
GLOBAL_CYCLES = CycleRecorder()
