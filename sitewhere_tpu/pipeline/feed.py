"""Pipelined host->device feeding: overlap staging with device compute.

VERDICT r2 finding: the single-chip headline path left the TPU ~3.5% busy —
the device program finishes in ~0.11 ms while ~2.9 ms of host pack + H2D
staging serialized ahead of every step. The fix is the classic
double-buffered accelerator input pipeline (the reference's nearest analog
is the DeviceEventBuffer linger thread that stages bulk writes ahead of
Mongo, DeviceEventBuffer.java:99-123 — applied here to the accelerator
boundary instead of the datastore):

  stager thread(s):  pack batch N+1 into a rotating wire-blob buffer and
                     start its H2D transfer (jax.device_put is async)
  step thread:       dispatch the fused step for batch N in submission
                     order (state donation serializes execution anyway)

Throughput becomes max(host_stage_time, device_step_time) instead of their
sum. With 2+ stagers, pack of batch N+2 also overlaps the (possibly
synchronous) transfer of batch N+1.

Ordering: steps are dispatched strictly in submission order (sequence
numbers; the step thread waits for the next sequence), so per-device event
order — the bus's per-key ordering contract — is preserved even though
stagers pack concurrently.
"""

from __future__ import annotations

import heapq
import queue
import threading
import time
from typing import List, Optional

import jax
import numpy as np

from sitewhere_tpu.ops.pack import EventBatch, batch_to_blob
from sitewhere_tpu.runtime.faults import FaultError, fault_point


def _stage_window(depth: int, engine) -> int:
    """How far ahead of the dispatch cursor a stager may run (allowed:
    seq - next_step <= window). Bounded by the engine's H2D staging-ring
    depth: with window <= ring_depth - 1, the slots held by sequences
    LATER than the earliest unstaged one can never fill the ring, so the
    ordered ring grant (pipeline/staging.py) always reaches it — the
    pigeonhole half of the deadlock-freedom argument. Ring depth 1
    degenerates to window 0: stage strictly in dispatch order (today's
    serial transfer behavior, the differential-test baseline)."""
    ring_depth = int(getattr(engine, "h2d_buffer_depth", depth))
    return min(max(1, depth), max(0, ring_depth - 1))


class StepFuture:
    """Result handle for one pipelined submit."""

    __slots__ = ("_event", "_outputs", "_error")

    def __init__(self):
        self._event = threading.Event()
        self._outputs = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        """The step's ProcessOutputs (dispatch-complete, not necessarily
        device-complete — block_until_ready a field for that)."""
        if not self._event.wait(timeout):
            raise TimeoutError("step not dispatched within timeout")
        if self._error is not None:
            raise self._error
        return self._outputs

    def _resolve(self, outputs=None, error: Optional[BaseException] = None):
        self._outputs = outputs
        self._error = error
        self._event.set()


class _PrePackedBlob:
    """A host wire blob that arrived already packed (a feeder's remote
    pack, feeders/service.py): the stager skips the pack stage and goes
    straight to the staging-ring grant + H2D."""

    __slots__ = ("blob", "n_events")

    def __init__(self, blob: np.ndarray, n_events: int):
        self.blob = blob
        self.n_events = int(n_events)


class PipelinedSubmitter:
    """Stage-ahead feeder for a PipelineEngine.

    `submit(batch)` enqueues and returns a StepFuture immediately (blocking
    only when `depth` batches are already in flight — natural backpressure).
    `stagers` host threads pack + device_put ahead; one step thread
    dispatches in order. Call `flush()` to drain and get the last outputs,
    `close()` to stop the threads.

    Works with the single-chip PipelineEngine (the sharded engine's
    submit() already overlaps routing with the previous step's execution
    because dispatch is async; its host routing is a single fused native
    pass — see parallel/router.py route_batch).
    """

    def __init__(self, engine, depth: int = 3, stagers: int = 2):
        self.engine = engine
        self.depth = max(1, depth)
        self._in: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._ready_lock = threading.Condition()
        self._ready: List = []          # heap of (seq, blob, n, future)
        self._next_seq = 0              # next sequence to assign
        self._next_step = 0             # next sequence to dispatch
        self._dispatched = 0            # steps whose dispatch has RETURNED
        self._stage_window = _stage_window(self.depth, engine)
        self._stop = threading.Event()
        self._close_lock = threading.Lock()  # atomic submit-vs-close gate
        self._stagers = [
            threading.Thread(target=self._stage_loop, name=f"feed-stage-{i}",
                             daemon=True)
            for i in range(max(1, stagers))]
        self._step_thread = threading.Thread(target=self._step_loop,
                                             name="feed-step", daemon=True)
        for t in self._stagers:
            t.start()
        self._step_thread.start()

    # -- producer ----------------------------------------------------------
    def submit(self, batch: EventBatch, age=None) -> StepFuture:
        fut = StepFuture()
        item = (self._alloc_seq(), batch, fut, age)
        # closure check and enqueue are atomic under _close_lock: close()
        # sets _stop under the same lock, so once close() proceeds to
        # drain, no producer can slip an item into the unattended queue
        # (a sleep-based window would lose the future forever on a
        # descheduled producer). The lock is never held across a blocking
        # put — full queues back off outside it.
        while True:
            with self._close_lock:
                if self._stop.is_set():
                    raise RuntimeError("submitter closed")
                try:
                    self._in.put_nowait(item)
                    return fut
                except queue.Full:
                    pass
            time.sleep(0.005)

    def _alloc_seq(self) -> int:
        with self._ready_lock:
            seq = self._next_seq
            self._next_seq += 1
            return seq

    def submit_blob(self, blob: np.ndarray, n_events: int,
                    age=None) -> StepFuture:
        """Enqueue a PRE-PACKED host wire blob (a feeder's remote pack,
        feeders/service.py): same ordered stage->dispatch path as
        submit(), minus the pack stage — interleaves correctly with
        concurrent submit() calls because both draw from the one
        sequence counter."""
        return self.submit(_PrePackedBlob(blob, n_events), age=age)

    # -- stager ------------------------------------------------------------
    def _stage_loop(self) -> None:
        while not self._stop.is_set():
            try:
                seq, batch, fut, age = self._in.get(timeout=0.1)
            except queue.Empty:
                continue
            # Bound the staged-ahead window: without this the ready heap
            # (and its device-resident blobs) would grow without limit
            # whenever staging outpaces dispatch, and a staging-ring slot
            # could be repacked while its H2D copy was still in flight.
            # The window is additionally capped at h2d_buffer_depth - 1
            # (_stage_window) so the ordered ring grant always reaches
            # the earliest unstaged sequence — the deadlock-freedom
            # invariant of the on-device staging ring.
            with self._ready_lock:
                while (not self._stop.is_set()
                       and seq - self._next_step > self._stage_window):
                    self._ready_lock.wait(timeout=0.1)
            if self._stop.is_set():
                fut._resolve(error=RuntimeError("submitter closed"))
                continue
            try:
                fault_point("feeder_thread_death")
                # flight record opened HERE on the stager thread and
                # handed to the step thread inside the heap item — the
                # explicit trace-context handoff that thread-local span
                # stacks cannot express. pack/guard/h2d land on this
                # thread; dispatch lands on the step thread; both sides
                # share one monotonic clock so overlap is computable.
                rec = self.engine.flight.begin_step(engine=self.engine.name)
                if age is not None:
                    # the ingest-age sidecar crosses threads on the record
                    # itself, exactly like the stage timeline
                    rec.age = age
                if isinstance(batch, _PrePackedBlob):
                    # a feeder's remote pack: no pack stage on this host —
                    # the blob goes straight to the ring grant + H2D, the
                    # whole point of the disaggregated fleet
                    blob = np.ascontiguousarray(batch.blob)
                    n = batch.n_events
                else:
                    buf = self.engine._staging_blob_buffer(batch,
                                                           flight_rec=rec)
                    rec.begin_stage("pack")
                    blob = batch_to_blob(batch, out=buf)
                    rec.end_stage("pack")
                    n = int(np.asarray(batch.valid).sum())
                # acquire an on-device staging-ring slot (granted in seq
                # order; backpressure when all h2d_buffer_depth transfers
                # are in flight) and start the H2D transfer — on async
                # runtimes it overlaps both other stagers' packs and
                # device compute. stage_blob arms the h2d_error fault
                # point with bounded retry/backoff and notes the host
                # blob-ring guard; submit_blob releases the slot with the
                # step's output as the reuse guard.
                dev_blob = self.engine.stage_blob(blob, flight_rec=rec,
                                                  order=seq)
                item = (seq, dev_blob, n, fut, rec, None)
            except BaseException as exc:  # surface through the future
                item = (seq, None, 0, fut, None, exc)
            with self._ready_lock:
                heapq.heappush(self._ready, item)
                self._ready_lock.notify_all()
            exc = item[5]
            if (isinstance(exc, FaultError)
                    and exc.point == "feeder_thread_death"):
                # drill: the batch's error is already in the heap (the
                # future resolves, the batch parks downstream) and THEN
                # this stager dies for real — remaining stagers carry on
                raise exc

    # -- step dispatcher ---------------------------------------------------
    def _step_loop(self) -> None:
        from collections import deque

        executing: deque = deque()
        while not self._stop.is_set():
            with self._ready_lock:
                while not (self._ready
                           and self._ready[0][0] == self._next_step):
                    if self._stop.is_set():
                        return
                    self._ready_lock.wait(timeout=0.1)
                seq, dev_blob, n, fut, rec, exc = heapq.heappop(self._ready)
                self._next_step += 1
            outputs = None
            try:
                if exc is None:
                    outputs = self.engine.submit_blob(
                        dev_blob, n_events=n, flight_rec=rec)
            except BaseException as step_exc:
                exc = step_exc
            finally:
                with self._ready_lock:
                    self._dispatched += 1
                    self._ready_lock.notify_all()
            if outputs is None:
                fut._resolve(error=exc)
                continue
            fut._resolve(outputs)
            # bound the device-side queue to `depth` in-flight steps:
            # keeps memory bounded AND guarantees a staging-ring slot's
            # H2D transfer finished before a stager can recycle it
            # (step N executed => its input was consumed)
            executing.append(outputs.processed)
            if len(executing) > self.depth:
                try:
                    executing.popleft().block_until_ready()
                except Exception:
                    pass  # a failed earlier step already surfaced there

    # -- draining ----------------------------------------------------------
    def flush(self, timeout: Optional[float] = 60.0) -> None:
        """Wait until every submitted batch's dispatch has RETURNED (so a
        direct engine.submit() afterwards cannot overtake a pipelined
        batch). Keep the StepFuture of your last submit if you need its
        outputs."""
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        with self._ready_lock:
            target = self._next_seq
            while self._dispatched < target:
                remaining = (None if deadline is None
                             else deadline - _time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError("pipelined flush timed out")
                self._ready_lock.wait(timeout=0.05 if remaining is None
                                      else min(0.05, remaining))

    def close(self) -> None:
        with self._close_lock:
            self._stop.set()
        # past this point submit() can only raise: nothing new enqueues
        with self._ready_lock:
            self._ready_lock.notify_all()
        for t in self._stagers:
            t.join(timeout=5.0)
        self._step_thread.join(timeout=5.0)
        # resolve anything still queued or staged so no caller blocks
        # forever on a future the stopped threads will never touch
        leftovers = []
        while True:
            try:
                leftovers.append(self._in.get_nowait())
            except queue.Empty:
                break
        with self._ready_lock:
            while self._ready:
                leftovers.append(heapq.heappop(self._ready))
        for item in leftovers:
            fut = item[2] if len(item) == 4 else item[3]
            if not fut.done():
                fut._resolve(error=RuntimeError("submitter closed"))
            # staged-but-never-dispatched blobs still hold ring slots;
            # hand them back (guard-free) so a later submitter over the
            # same engine isn't starved
            staged = item[1] if len(item) == 6 else None
            slot = getattr(staged, "slot", None)
            if slot is not None:
                self.engine.staging_ring.release(slot)


class ShardedPipelinedSubmitter:
    """Stage-ahead feeder for the ShardedPipelineEngine.

    The sharded submit() serializes route -> device_put -> dispatch on
    the caller thread; the H2D staging alone can dwarf the device
    step, leaving the mesh idle between submits. This
    feeder applies the same double-buffered discipline PipelinedSubmitter
    gives the single-chip engine, adapted to the sharded path's extra
    invariant — ROUTING IS STATEFUL (it consumes and produces the
    engine's overflow backlog, and per-device order requires requeued
    rows to ride the next routed batch):

      stagers:   take batch N; PREPARE it in strict submission order (a
                 routing turnstile). With device routing on (the default
                 on real multi-shard meshes) preparing is pack + a cheap
                 lane-fit guard — the mesh itself routes the rows inside
                 the step (ops/route.py); otherwise the host arena route
                 runs here. Then start the mesh transfer
                 (engine.stage_prepared, async device_put) concurrently
                 with other stagers' prep/transfers
      step thread: dispatch staged steps in submission order (state
                 donation serializes device execution anyway)

    Backpressure parity with submit(): when the backlog exceeds
    `engine.max_overflow_events` at routing time, drain blobs (backlog
    only, no new rows) are staged as extra steps under the same routing
    turn; their alerts stash on the engine's pending-alert buffer exactly
    like submit()'s internal drain.

    Single-controller only: a multi-host cluster feeds through
    parallel/cluster.py's lockstep loop (drain steps here would desync
    the collective count across hosts).

    `submit(batch)` returns a StepFuture resolving to (routed view,
    outputs) — the same pair engine.submit returns.
    """

    def __init__(self, engine, depth: int = 3, stagers: int = 2):
        if engine.is_multiprocess:
            raise RuntimeError(
                "ShardedPipelinedSubmitter is single-controller only; "
                "multi-host clusters feed through the lockstep step loop "
                "(parallel/cluster.py)")
        self.engine = engine
        self.depth = max(1, depth)
        self._in: "queue.Queue" = queue.Queue(maxsize=self.depth)
        self._ready_lock = threading.Condition()
        self._ready: List = []      # heap of (seq, staged_list, fut, exc)
        self._next_seq = 0
        self._next_route = 0        # routing turnstile position
        self._next_step = 0
        self._dispatched = 0
        self._stage_window = _stage_window(self.depth, engine)
        self._stop = threading.Event()
        self._close_lock = threading.Lock()
        self._stagers = [
            threading.Thread(target=self._stage_loop,
                             name=f"shard-feed-stage-{i}", daemon=True)
            for i in range(max(1, stagers))]
        self._step_thread = threading.Thread(target=self._step_loop,
                                             name="shard-feed-step",
                                             daemon=True)
        for t in self._stagers:
            t.start()
        self._step_thread.start()

    # -- producer ----------------------------------------------------------
    def submit(self, batch: EventBatch, age=None) -> StepFuture:
        fut = StepFuture()
        item = (self._alloc_seq(), batch, fut, age)
        while True:
            with self._close_lock:
                if self._stop.is_set():
                    raise RuntimeError("submitter closed")
                try:
                    self._in.put_nowait(item)
                    return fut
                except queue.Full:
                    pass
            time.sleep(0.005)

    def _alloc_seq(self) -> int:
        with self._ready_lock:
            seq = self._next_seq
            self._next_seq += 1
            return seq

    # -- stager ------------------------------------------------------------
    def _stage_loop(self) -> None:
        while not self._stop.is_set():
            try:
                seq, batch, fut, age = self._in.get(timeout=0.1)
            except queue.Empty:
                continue
            # bound the staged-ahead window (see PipelinedSubmitter; the
            # h2d_buffer_depth - 1 cap keeps the staging ring's ordered
            # grant deadlock-free here too)
            with self._ready_lock:
                while (not self._stop.is_set()
                       and seq - self._next_step > self._stage_window):
                    self._ready_lock.wait(timeout=0.1)
            # routing turnstile: strict submission order — routing folds
            # in (and re-parks) the engine overflow backlog, so two
            # batches must never route concurrently or out of order
            with self._ready_lock:
                while (not self._stop.is_set()
                       and self._next_route != seq):
                    self._ready_lock.wait(timeout=0.1)
            if self._stop.is_set():
                fut._resolve(error=RuntimeError("submitter closed"))
                continue
            eng = self.engine
            staged = None
            exc: Optional[BaseException] = None
            try:
                try:
                    fault_point("feeder_thread_death")
                    # _prepare_step: with device routing on (the default
                    # on real multi-shard meshes) this is pack + the
                    # cheap lane-fit guard ONLY — the mesh does the
                    # bucketing in the step's prologue (ops/route.py),
                    # freeing stager CPU for persist/consumer work; the
                    # host arena route runs just for skewed spills
                    merged = eng.merge_pending_overflow(batch)
                    prepared, over = eng._prepare_step(merged, age=age)
                    eng.park_overflow(merged, over)
                    prepped = [prepared]
                    # backpressure: route drain blobs (backlog only) as
                    # extra steps under the same turn, like submit()
                    while eng.pending_overflow > eng.max_overflow_events:
                        backlog = eng.pending_overflow_batch()
                        eng.set_pending_overflow_batch(None)
                        dprep, dover = eng._prepare_step(backlog)
                        eng.park_overflow(backlog, dover)
                        prepped.append(dprep)
                finally:
                    with self._ready_lock:
                        self._next_route += 1
                        self._ready_lock.notify_all()
                # mesh transfers start here, OUTSIDE the turnstile: they
                # overlap other stagers' routing and the device compute.
                # The step's first blob takes a staging-ring slot in seq
                # order (backpressure edge); drain blobs bypass the ring
                # (use_ring=False) — they dispatch before this step's
                # heap push, so blocking on slots held by their own
                # siblings would self-deadlock (see stage_prepared)
                staged = [eng.stage_prepared(p, order=seq if i == 0
                                             else None,
                                             use_ring=(i == 0))
                          for i, p in enumerate(prepped)]
            except BaseException as stage_exc:
                exc = stage_exc
            with self._ready_lock:
                heapq.heappush(self._ready, (seq, staged, fut, exc))
                self._ready_lock.notify_all()
            if (isinstance(exc, FaultError)
                    and exc.point == "feeder_thread_death"):
                # drill: error item is in the heap (future resolves, the
                # routing turnstile already advanced in the finally) and
                # then this stager dies for real
                raise exc

    # -- step dispatcher ---------------------------------------------------
    def _step_loop(self) -> None:
        from collections import deque

        executing: deque = deque()
        while not self._stop.is_set():
            with self._ready_lock:
                while not (self._ready
                           and self._ready[0][0] == self._next_step):
                    if self._stop.is_set():
                        return
                    self._ready_lock.wait(timeout=0.1)
                seq, staged, fut, exc = heapq.heappop(self._ready)
                self._next_step += 1
            result = None
            try:
                if exc is None:
                    eng = self.engine
                    params = eng._ensure_params()
                    for s in staged[:-1]:
                        # drained steps' alerts stash exactly like
                        # submit()'s internal drain (the caller only
                        # sees the LAST step's outputs)
                        view, outputs = eng.dispatch_staged(params, s)
                        eng._stash_pending_alerts(
                            eng._materialize_routed(view, outputs))
                        eng.drain_steps += 1
                    result = eng.dispatch_staged(params, staged[-1])
            except BaseException as step_exc:
                exc = step_exc
            finally:
                with self._ready_lock:
                    self._dispatched += 1
                    self._ready_lock.notify_all()
            if result is None:
                fut._resolve(error=exc)
                continue
            fut._resolve(result)
            # bound the device-side queue to `depth` in-flight steps
            executing.append(result[1].processed)
            if len(executing) > self.depth:
                try:
                    executing.popleft().block_until_ready()
                except Exception:
                    pass  # a failed earlier step already surfaced there

    # -- draining ----------------------------------------------------------
    def flush(self, timeout: Optional[float] = 60.0) -> None:
        """Wait until every submitted batch's dispatch has RETURNED (a
        direct engine.submit() afterwards cannot overtake)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._ready_lock:
            target = self._next_seq
            while self._dispatched < target:
                remaining = (None if deadline is None
                             else deadline - time.monotonic())
                if remaining is not None and remaining <= 0:
                    raise TimeoutError("pipelined flush timed out")
                self._ready_lock.wait(timeout=0.05 if remaining is None
                                      else min(0.05, remaining))

    def close(self) -> None:
        with self._close_lock:
            self._stop.set()
        with self._ready_lock:
            self._ready_lock.notify_all()
        for t in self._stagers:
            t.join(timeout=5.0)
        self._step_thread.join(timeout=5.0)
        leftovers = []
        while True:
            try:
                leftovers.append(self._in.get_nowait())
            except queue.Empty:
                break
        with self._ready_lock:
            while self._ready:
                leftovers.append(heapq.heappop(self._ready))
        for item in leftovers:
            fut = item[2]
            if not fut.done():
                fut._resolve(error=RuntimeError("submitter closed"))
            # release ring slots of staged-but-never-dispatched steps
            # (ready-heap items carry a staged LIST; _in queue items
            # carry the raw EventBatch — skip those)
            if isinstance(item[1], list):
                for s in item[1]:
                    if getattr(s, "slot", None) is not None:
                        self.engine.staging_ring.release(s.slot)


class AdaptiveBatcher:
    """Latency-tier submitter: flush on fill OR linger deadline.

    The throughput tier (PipelinedSubmitter) maximizes events/sec by
    keeping full production batches in flight; a latency-sensitive source
    instead wants each event through ingest -> rules -> alert within a
    wall-clock budget (BASELINE's p99 < 10 ms). `offer(events, tokens)`
    buffers; a flusher thread submits the pending rows as soon as either
    (a) a full engine batch is pending — no point waiting — or (b) the
    OLDEST pending offer has waited `linger_ms`. Small batches keep the
    pack + H2D + step wall time in single-digit milliseconds (the blob is
    bytes-per-event * batch_size, so at 4096 rows the transfer is ~100x
    smaller than the 131k throughput batch), and the linger bound caps
    the queueing delay added on top.

    The engine is expected to be sized for the tier
    (``pipeline.mode = "latency"`` boots it at
    ``pipeline.latency_batch_size``); an engine-per-mode is the TPU
    reality — batch size is a compiled shape, not a runtime knob.

    ``adaptive=True`` turns on ADAPTIVE LINGER: an ``offer()`` delivers a
    complete burst, so the flusher dispatches as soon as anything is
    pending instead of always sleeping out the full linger window — the
    window only coalesces offers that arrive while a flush is already in
    flight (and ``linger_ms`` stays the fill-wait upper bound). On the
    latency tier the linger sleep was the second-largest constant in the
    end-to-end number after D2H fetches (docs/ALERT_LANES.md). Default
    off: the classic fixed linger maximizes coalescing for bursty
    multi-producer ingest.

    Kafka analog: linger.ms + batch.size on the reference's producers
    (the reference never surfaces an end-to-end latency tier; this
    exceeds it).
    """

    def __init__(self, engine, linger_ms: float = 2.0,
                 max_rows: Optional[int] = None, adaptive: bool = False):
        self.engine = engine
        self.linger_s = max(0.0, linger_ms) / 1000.0
        self.adaptive = adaptive
        self.max_rows = max_rows or engine.batch_size
        self._lock = threading.Condition()
        self._events: List = []
        self._tokens: List[str] = []
        self._futures: List[StepFuture] = []
        # (ingest stamp, event count) per offer — folded into one
        # AgeSidecar at flush so the age waterfall sees linger time
        self._ages: List = []
        self._oldest: Optional[float] = None
        self._stop = threading.Event()
        # steady-state accounting: flushes counts every engine flush this
        # batcher ran; warm() moves the cold-path work (jit compiles,
        # interner fills, thread ramp-up) BEFORE measurement and records
        # how many flushes were warmup, so a latency harness can report
        # percentiles over the steady-state window only
        self.flushes = 0
        self.warm_flushes = 0
        self._thread = threading.Thread(target=self._flush_loop,
                                        name="feed-latency", daemon=True)
        self._thread.start()

    @property
    def steady_flushes(self) -> int:
        """Flushes run after the last warm() — the steady-state window."""
        return max(0, self.flushes - self.warm_flushes)

    def warm(self, events, tokens, repeats: int = 2,
             timeout: float = 600.0) -> int:
        """Bring the latency tier to steady state for this traffic shape:
        run `repeats` full offer -> linger -> pack -> step -> materialized
        alerts cycles and mark them as warmup. The first cycle pays the
        jit compile of the engine's program for this batch shape and wire
        variant plus the interner fills; p99 percentiles measured AFTER
        warm() describe the steady-state path BASELINE's latency budget
        is about (a compile must never count against a 10 ms budget — it
        happens once per shape per process, not per event)."""
        import jax

        for _ in range(max(1, repeats)):
            fut = self.offer(events, tokens)
            for batch, outputs in fut.result(timeout=timeout):
                jax.block_until_ready(outputs.processed)
                self.engine.materialize_alerts(batch, outputs)
        with self._lock:
            self.warm_flushes = self.flushes
        return self.warm_flushes

    def offer(self, events, tokens, received_at=None) -> StepFuture:
        """Buffer events (parallel `tokens` list, one per event); the
        returned future resolves with the flush's list of
        (batch, outputs) pairs — one pair per engine batch the flush
        needed (usually one; a flush bigger than the engine batch packs
        into several) — once every fused step covering these rows has
        been dispatched. `received_at` is the offer's ingest stamp
        (time.perf_counter at the receive edge); None stamps now."""
        fut = StepFuture()
        if not events:
            fut._resolve([])  # nothing to wait for; don't arm the linger
            return fut
        with self._lock:
            if self._stop.is_set():
                raise RuntimeError("batcher closed")
            self._events.extend(events)
            self._tokens.extend(tokens)
            self._ages.append((received_at if received_at is not None
                               else time.perf_counter(), len(events)))
            self._futures.append(fut)
            if self._oldest is None:
                self._oldest = time.monotonic()
            self._lock.notify_all()
        return fut

    def _flush_loop(self) -> None:
        while True:
            with self._lock:
                while not self._stop.is_set():
                    if self._oldest is not None:
                        # adaptive linger: pending offers are complete
                        # bursts — dispatch now; coalescing happens
                        # naturally while a flush is in flight
                        if self.adaptive:
                            break
                        wait = self._oldest + self.linger_s - time.monotonic()
                        if wait <= 0 or len(self._events) >= self.max_rows:
                            break
                        self._lock.wait(timeout=wait)
                    else:
                        # both state transitions (offer, close) notify —
                        # no poll timeout needed while idle
                        self._lock.wait()
                if self._stop.is_set() and not self._events:
                    return
                events, self._events = self._events, []
                tokens, self._tokens = self._tokens, []
                futures, self._futures = self._futures, []
                ages, self._ages = self._ages, []
                self._oldest = None
            self._flush(events, tokens, futures, ages)

    def _flush(self, events, tokens, futures, ages=()) -> None:
        from sitewhere_tpu.runtime.eventage import AgeSidecar

        age = AgeSidecar()
        for stamp, n in ages:
            age.add(stamp, n)
        try:
            # the whole flush's sidecar rides the FIRST batch (a flush
            # rarely spans batches; splitting per-offer stamps across
            # them would be guesswork, double-attaching would double-count)
            results = [self.engine.submit_routed(
                           batch, age=(age if i == 0 else None))
                       for i, batch in enumerate(
                           self.engine.packer.pack_events(events, tokens))]
            with self._lock:
                self.flushes += 1
            for fut in futures:
                fut._resolve(results)
        except BaseException as exc:
            for fut in futures:
                fut._resolve(error=exc)

    def close(self) -> None:
        with self._lock:
            self._stop.set()
            self._lock.notify_all()
        self._thread.join(timeout=10.0)
