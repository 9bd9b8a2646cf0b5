"""Reduction of a JAX profiler trace to device metrics.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<i>``; their ``XLA Ops`` line holds
one event per operation run on the chip, named by its HLO text
(``%hop_fused.7 = (...) custom-call(...)``), with start and duration in
nanoseconds on the same clock as the host planes. Busy time is the union
of those intervals; a kernel's time is the sum of the durations of the
events whose instruction is named after it. The host span
``bench.window`` bounds the traced window; the host spans
``bench.engine_batch`` (one per engine batch, with its ``batch`` number)
attribute device time to batches and label the idle gaps.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib

import numpy as np

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
BATCH_SPAN = "bench.engine_batch"
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


@dataclasses.dataclass
class Trace:
    start: np.ndarray        # (n_ops,) ns, clipped to the window
    dur: np.ndarray          # (n_ops,) ns
    device: np.ndarray       # (n_ops,) int
    name: np.ndarray         # (n_ops,) index into ``names``
    names: list              # HLO instruction names (``hop_fused.7``)
    spans: list              # (name, start_ns, end_ns, stats) host spans
    t0_ns: float             # the traced window
    t1_ns: float
    n_devices: int

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def busy_intervals(self, device: int) -> np.ndarray:
        """(k, 2) merged [start, end) intervals in which ``device`` ran an
        operation."""
        m = self.device == device
        s, e = self.start[m], self.start[m] + self.dur[m]
        if not s.size:
            return np.zeros((0, 2))
        o = np.argsort(s, kind="stable")
        s, e = s[o], np.maximum.accumulate(e[o])
        new = np.ones(s.size, bool)
        new[1:] = s[1:] > e[:-1]
        first = np.flatnonzero(new)
        last = np.r_[first[1:] - 1, s.size - 1]
        return np.stack([s[first], e[last]], axis=1)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices used."""
        tot = sum(float(np.sum(iv[:, 1] - iv[:, 0])) for iv in
                  (self.busy_intervals(d) for d in range(self.n_devices)))
        return tot / max(1, self.n_devices) / 1e9

    def _ops_named(self, kernel: str) -> np.ndarray:
        ids = [i for i, n in enumerate(self.names)
               if n == kernel or n.startswith(kernel + ".")]
        return np.isin(self.name, ids)

    def kernel_s(self, kernel: str, within=None) -> float:
        """Device seconds of the operations named ``kernel``, summed over
        the devices used; with ``within`` ((start, end) ns pairs), only
        those that start inside one of them."""
        m = self._ops_named(kernel)
        if within is not None:
            inside = np.zeros_like(m)
            for s, e in within:
                inside |= (self.start >= s) & (self.start < e)
            m &= inside
        return float(np.sum(self.dur[m])) / 1e9

    def batches_inside(self) -> dict:
        """{batch number: (start, end)} of the engine batches whose whole
        span lies inside the window."""
        return {st["batch"]: (s, e) for n, s, e, st in self.spans
                if n == BATCH_SPAN and "batch" in st
                and s >= self.t0_ns and e <= self.t1_ns}

    def gaps(self, device: int = 0) -> list:
        """(start_ns, end_ns) idle gaps of ``device`` in the window."""
        iv = self.busy_intervals(device)
        edges = np.r_[self.t0_ns, iv.reshape(-1), self.t1_ns].reshape(-1, 2)
        return [(float(s), float(e)) for s, e in edges if e > s]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (ops that contain
        others, such as a while loop, left out), and the idle time split
        by the host span it overlaps; idle time outside every span is
        ``between engine batches`` (queue, submit, hand-off of answers)."""
        tot = np.bincount(self.name, weights=self.dur,
                          minlength=len(self.names))
        order = [i for i in np.argsort(-tot)
                 if tot[i] > 0 and not self.names[i].startswith(CONTAINERS)]
        spans = collections.defaultdict(list)
        for n, s, e, _ in self.spans:
            if n != WINDOW_SPAN:
                spans[n].append((s, e))
        idle = collections.Counter()
        for gs, ge in self.gaps():
            covered = 0.0
            for n, iv in spans.items():
                part = _overlap(iv, gs, ge)
                idle[n] += part
                covered += part
            idle["between engine batches"] += (ge - gs) - covered
        return {
            "device_ops": [[self.names[i], float(tot[i]) / 1e9]
                           for i in order[:top]],
            "idle_gaps": [[n, d / 1e9] for n, d in idle.most_common(top)
                          if d > 0],
        }


def _overlap(intervals: list, s: float, e: float) -> float:
    """Length of [s, e) covered by the union of ``intervals``."""
    cut = sorted((max(a, s), min(b, e)) for a, b in intervals
                 if b > s and a < e)
    tot, end = 0.0, s
    for a, b in cut:
        a = max(a, end)
        if b > a:
            tot += b - a
            end = b
    return tot


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def reduce_file(path, n_devices: int) -> Trace:
    """The Trace of the window in ``path`` (an ``.xplane.pb``): the host
    span ``bench.window`` bounds it, and device ops are clipped to it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           _stats(ev)) for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in {path}")
    _, t0, t1, _ = windows[0]
    index: dict = {}
    start, dur, device, name = [], [], [], []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
        if dev >= n_devices:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = ev.start_ns
                e = s + ev.duration_ns
                if e <= t0 or s >= t1:
                    continue
                full = ev.name
                key = full[:full.find(" ")] if " " in full else full
                start.append(max(s, t0))
                dur.append(min(e, t1) - max(s, t0))
                device.append(dev)
                name.append(index.setdefault(key.lstrip("%"), len(index)))
    return Trace(np.asarray(start, np.float64), np.asarray(dur, np.float64),
                 np.asarray(device, np.int32), np.asarray(name, np.int64),
                 list(index), [s for s in spans if s[0] != WINDOW_SPAN],
                 t0, t1, n_devices)


def reduce_dir(trace_dir, n_devices: int) -> Trace:
    """The Trace of the one capture under ``trace_dir``."""
    files = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], n_devices)
