"""What every mix shares: the mix file (`traffic/<name>.json`) of
parameters, the size of its pool, the publisher that keeps a backlog and
the sampler of the commit edge.

The records themselves are made from the mix, a world and a seed by the
deployment's module (`deployments/<name>.py`), or by the generator the mix
names (`traffic/<generator>.py`): `make_traffic` and `encode_records`.
They go on the tenant's decoded-events topic, keyed by device token: the
boundary where the event-sources service hands records to inbound
processing.
"""

from __future__ import annotations

import bisect
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark import loader

# how often the publisher looks at the backlog: a consumer cycle takes
# seconds, so this keeps the backlog full at a small cost in the GIL
CHECK_S = 0.005


def load_mix(name: str, overrides: Optional[Dict] = None) -> Dict:
    with open(os.path.join(loader.TRAFFIC_DIR, f"{name}.json")) as fh:
        mix = json.load(fh)
    mix.update(overrides or {})
    return mix


def pool_records(mix: Dict, per: int) -> int:
    """Records the pool holds, at `per` events to a record: warm-up plus
    what the window can take (at least four times the rate served today,
    over the longest window)."""
    return (int(mix["warmup_records"])
            + -(-int(mix["pool_events"]) // per))


class Publisher:
    """Appends pool records to the decoded-events topic from its own
    thread, remembering every record's (partition, offset), and keeps at
    least `backlog_records` records unpolled by the consumer group."""

    def __init__(self, topic, group, records, *, backlog_records: int):
        self.topic = topic
        self.group = group
        self.records = records
        self.next = 0
        self.backlog_records = backlog_records
        n = len(records)
        self.part = np.full(n, -1, np.int64)
        self.offset = np.full(n, -1, np.int64)
        # per partition: offset of its first record here, and the record
        # numbers it holds in publish order (the committed-prefix index)
        self.base_offset: Dict[int, int] = {}
        self.by_part: Dict[int, List[int]] = {}
        self.exhausted_at = None
        self.backlog_low = 0     # checks at which the backlog had run dry
        self.filled = threading.Event()   # the backlog stood full once
        self._stop = threading.Event()
        self._thread = None

    def publish(self, upto: int) -> None:
        """Publish records [next, upto) in one bulk append per partition
        (as the networked bus edge does), noting each one's offset: this
        thread is the topic's only writer."""
        rows = range(self.next, upto)
        batch = [self.records[r] for r in rows]
        ends = self.topic.end_offsets()
        self.topic.publish_many(batch)
        for r, (key, _) in zip(rows, batch):
            part = self.topic.partition_for(key)
            self.part[r], self.offset[r] = part, ends[part]
            ends[part] += 1
            if part not in self.by_part:
                self.base_offset[part] = self.offset[r]
                self.by_part[part] = []
            self.by_part[part].append(r)
        self.next = upto

    def committed_prefix(self, committed) -> int:
        """Largest k such that records [0, k) are all committed under the
        committed offsets `committed` (one partition commits in order)."""
        k = self.next
        for p, rows in list(self.by_part.items()):
            i = committed[p] - self.base_offset[p]
            if i < len(rows):
                k = min(k, rows[max(i, 0)])
        return k

    def publish_now(self, upto: int) -> None:
        """Publish records [next, upto) at once (warm-up)."""
        if upto > self.next:
            self.publish(upto)

    def unpolled(self) -> int:
        ends = self.topic.end_offsets()
        pos = self.group.position
        return int(sum(ends) - sum(pos[:len(ends)]))

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, name="bench-publish",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)

    def _run(self) -> None:
        n = len(self.records)
        while not self._stop.is_set():
            if self.next >= n:
                if self.exhausted_at is None:
                    self.exhausted_at = time.perf_counter()
                self._stop.wait(0.01)
                continue
            have = self.unpolled()
            if have == 0 and self.filled.is_set():
                self.backlog_low += 1
            want = min(self.backlog_records - have, n - self.next)
            if want <= 0:
                self.filled.set()
                self._stop.wait(CHECK_S)
                continue
            self.publish(self.next + want)


class CommitSampler:
    """The effect edge: the inbound group's committed offsets, read from
    the harness's own thread every `period_s`, kept as change points (the
    commit edges); the topic's lag is sampled alongside."""

    def __init__(self, topic, group, period_s: float = 0.001):
        self.topic = topic
        self.group = group
        self.period_s = period_s
        self.times: List[float] = []
        self.committed: List[Tuple[int, ...]] = []
        self.lag_t: List[float] = []
        self.lag: List[int] = []
        self._stop = threading.Event()
        self._thread = None

    def sample(self) -> None:
        now = time.perf_counter()
        committed = tuple(self.group.committed)
        if not self.committed or committed != self.committed[-1]:
            self.times.append(now)
            self.committed.append(committed)
        if not self.lag_t or now - self.lag_t[-1] >= 0.01:
            self.lag_t.append(now)
            self.lag.append(int(sum(self.topic.end_offsets())
                                - sum(committed)))

    def start(self) -> None:
        self.sample()
        self._thread = threading.Thread(target=self._run, name="bench-sampler",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
        self.sample()

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            self.sample()

    def wait_edge(self, after: float, timeout_s: float) -> Optional[float]:
        """Time of the first commit edge later than `after`, waiting for
        it until `timeout_s` past `after`; None when none came."""
        deadline = max(after, time.perf_counter()) + timeout_s
        while True:
            times = self.times
            i = bisect.bisect_right(times, after)
            if i < len(times):
                return times[i]
            if time.perf_counter() > deadline:
                return None
            time.sleep(0.0005)

    def commit_times(self, part: np.ndarray, offset: np.ndarray) -> np.ndarray:
        """First sample time at which each (partition, offset) record was
        committed; NaN for records never seen committed."""
        times = np.asarray(self.times)
        table = np.asarray(self.committed, np.int64)   # [S, P]
        out = np.full(part.shape[0], np.nan)
        for p in np.unique(part[part >= 0]).tolist():
            rows = np.nonzero(part == p)[0]
            k = np.searchsorted(table[:, p], offset[rows], side="right")
            ok = k < len(times)
            out[rows[ok]] = times[k[ok]]
        return out
