"""Reduction of a profiler capture by the program's own spans and scopes.

``lib/trace.py`` reduces a capture with the benchmark's spans alone
(``bench.window``, ``bench.engine_batch``) and names each device op by
its HLO instruction. Where the program records its own spans
(``pipeann.<layer>.<stage>``, ``repro.utils.spans``) and name scopes,
this reduction goes further: each idle gap of the chip is put down to the
innermost program span open on the thread that runs the engine batch,
or, where that thread is in none, to the innermost one open on another
thread (admission pricing on a caller's thread), or else to the
benchmark's span as before; and each device op is named by its innermost
``pipeann.`` scope (``pipeann.hop.merge/fusion.94``), read from the
``tf_op`` stat of its event metadata, or else by its program on the
device's ``XLA Modules`` line (``jit_run_hops/fusion.1``). JAX's
persistent compile cache leaves op metadata out of its key, so an
executable it held from code without the scopes runs with unscoped
names, and its ops are named by program.
"""
from __future__ import annotations

import collections
import dataclasses
import pathlib

import numpy as np

from lib import trace
from lib.trace import BATCH_SPAN, CONTAINERS, DEVICE_PREFIX, OPS_LINE, \
    WINDOW_SPAN

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX = "pipeann."
BETWEEN = "between engine batches"

# the host layers whose idle share the reduction reports:
# (span name prefixes, span names left out)
HOST_LAYERS = {
    "prefilter": (("pipeann.prefilter.",), ()),
    "hop_driver": (("pipeann.hops.",), ()),
    "assembly": (("pipeann.index.", "pipeann.engine.route",
                  "pipeann.engine.collect"), ()),
    "server": (("pipeann.server.",), ("pipeann.server.execute",)),
}


@dataclasses.dataclass
class SpanTrace(trace.Trace):
    host_spans: list = ()    # (name, start_ns, end_ns, stats, thread) of
                             # the program's ``pipeann.`` spans
    engine_thread: object = None   # the thread ``bench.engine_batch`` ran on
    labels: list = None      # op names qualified by scope or program
    label: np.ndarray = None  # (n_ops,) index into ``labels``

    def scope_s(self, where: tuple, within=None) -> float:
        """Device seconds of the operations named by one of ``where``:
        their innermost program scope (``pipeann.hop.merge``) or, for ops
        without one, their program (``jit__pq_topl``); ops that contain
        others (a scan's ``while``) left out, as their body's ops are
        counted; summed over the devices used; with ``within`` ((start,
        end) ns pairs), only those that start inside one of them."""
        if self.label is None:
            return 0.0
        ids = [i for i, n in enumerate(self.labels)
               if n.rsplit("/", 1)[0] in where
               and not n.rsplit("/", 1)[-1].startswith(CONTAINERS)]
        m = np.isin(self.label, ids)
        if within is not None:
            inside = np.zeros_like(m)
            for s, e in within:
                inside |= (self.start >= s) & (self.start < e)
            m &= inside
        return float(np.sum(self.dur[m])) / 1e9

    def idle_by_span(self) -> collections.Counter:
        """Idle nanoseconds of device 0 in the window, by the innermost
        program span (see the module's docstring), the rest by the
        benchmark's span or ``between engine batches``; the parts sum to
        the idle time."""
        # cut the window at every span edge; each piece takes one label
        # and the device's idle time inside it (times relative to t0)
        t0, w = self.t0_ns, self.t1_ns - self.t0_ns
        bench = [(n, s - t0, e - t0) for n, s, e, _ in self.spans
                 if n != WINDOW_SPAN]
        prog = [(n, s - t0, e - t0, th) for n, s, e, _, th
                in self.host_spans]
        cuts = np.unique(np.clip(np.array(
            [0.0, w] + [x for sp in bench + prog for x in sp[1:3]]), 0, w))
        busy = self.busy_intervals(0) - t0
        idle = _idle_upto(busy, cuts[1:]) - _idle_upto(busy, cuts[:-1])
        label = _innermost(prog, cuts, self.engine_thread)
        out = collections.Counter()
        for i in np.flatnonzero(idle > 0):
            name = label[i]
            if name is None:
                mid = (cuts[i] + cuts[i + 1]) / 2
                name = next((n for n, s, e in bench if s <= mid < e),
                            BETWEEN)
            out[name] += float(idle[i])
        return out

    def idle_in_batches_s(self) -> float:
        """Idle seconds of device 0 inside the engine batches' spans."""
        batches = [(s, e) for n, s, e, _ in self.spans if n == BATCH_SPAN]
        return sum(trace._overlap(batches, s, e)
                   for s, e in self.gaps()) / 1e9

    def idle_share(self, spans: tuple, exclude: tuple = ()):
        """Percent of the window in which device 0 is idle and the host is
        in a program span whose name starts with one of ``spans`` (and is
        not one of ``exclude``), by :meth:`idle_by_span`; None on a trace
        without program spans."""
        if not self.host_spans or self.window_s <= 0:
            return None
        ns = sum(d for n, d in self.idle_by_span().items()
                 if n.startswith(spans) and n not in exclude)
        return 100.0 * ns / 1e9 / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """As :meth:`lib.trace.Trace.breakdown`, with each device op named
        by its program scope or program, and the idle time split by
        :meth:`idle_by_span`; a trace without program spans or scopes
        reduces exactly as there."""
        if self.label is None and not self.host_spans:
            return super().breakdown(top)
        tot = np.bincount(self.label, weights=self.dur,
                          minlength=len(self.labels))
        order = [i for i in np.argsort(-tot)
                 if tot[i] > 0 and not self.labels[i].rsplit(
                     "/", 1)[-1].startswith(CONTAINERS)]
        idle = self.idle_by_span()
        return {
            "device_ops": [[self.labels[i], float(tot[i]) / 1e9]
                           for i in order[:top]],
            "idle_gaps": [[n, d / 1e9] for n, d in idle.most_common(top)
                          if d > 0],
        }


def _idle_upto(busy: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Idle time in [0, t) for each t, given merged busy intervals."""
    if not busy.size:
        return t.astype(np.float64)
    s, e = busy[:, 0], busy[:, 1]
    before = np.r_[0.0, np.cumsum(e - s)]        # busy before interval k
    j = np.searchsorted(s, t, side="right")      # intervals begun by t
    k = np.maximum(j - 1, 0)
    busy_t = np.where(j > 0, before[k] + np.clip(t - s[k], 0.0, e[k] - s[k]),
                      0.0)
    return t - busy_t


def _innermost(prog: list, cuts: np.ndarray, engine_thread) -> list:
    """For each piece [cuts[i], cuts[i+1]): the name of the innermost
    program span open on ``engine_thread``, else of the innermost open on
    another thread (the latest to open), else None."""
    n = cuts.size - 1
    by_thread = collections.defaultdict(list)
    for sp in prog:
        by_thread[sp[3]].append(sp)
    open_ = {}                   # thread -> (span index, its start) per piece
    for th, sps in by_thread.items():
        cur, opened = np.full(n, -1), np.full(n, -np.inf)
        # a span that opens later overwrites the one it nests in
        for k, (_, s, e, _) in sorted(enumerate(sps),
                                      key=lambda x: (x[1][1], -x[1][2])):
            lo, hi = np.searchsorted(cuts, [s, e])
            cur[lo:hi], opened[lo:hi] = k, s
        open_[th] = (cur, opened)
    mine = open_.pop(engine_thread, None)
    label = [None] * n
    for i in range(n):
        if mine is not None and mine[0][i] >= 0:
            label[i] = by_thread[engine_thread][mine[0][i]][0]
            continue
        cand = [(o[i], by_thread[th][c[i]][0])
                for th, (c, o) in open_.items() if c[i] >= 0]
        if cand:
            label[i] = max(cand)[1]
    return label


def _innermost_scope(tf_op: str):
    """The innermost ``pipeann.`` component of an op's name path."""
    parts = [p for p in tf_op.split("/") if p.startswith(PROGRAM_PREFIX)]
    return parts[-1] if parts else None


def _op_scopes(path) -> dict:
    """{(device plane name, line number): [(the event's name, its
    innermost program scope or None), per event of that ``XLA Ops`` line,
    in order]}, from each event's metadata (its name, and the ``tf_op``
    stat); {} where the installed protobuf runtime cannot read the file
    (``lib/xplane.py``)."""
    from google.protobuf.message import DecodeError

    from lib import xplane
    try:
        space = xplane.read(path)
    except DecodeError:
        return {}
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = {}
        for k, md in plane.event_metadata.items():
            tf_op = next((st.str_value for st in md.stats
                          if stat_names.get(st.metadata_id) == "tf_op"), "")
            meta[k] = (md.name, _innermost_scope(tf_op))
        for i, line in enumerate(plane.lines):
            if line.name == OPS_LINE:
                out[plane.name, i] = [meta.get(ev.metadata_id, ("", None))
                                      for ev in line.events]
    return out


def _programs(plane, starts: np.ndarray) -> list:
    """The program (``jit_run_hops``) whose run on the device covers each
    time in ``starts``, from the plane's ``XLA Modules`` line; None where
    none does."""
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   ev.name.split("(")[0]) for line in plane.lines
                  if line.name == MODULES_LINE for ev in line.events)
    if not mods:
        return [None] * starts.size
    j = np.searchsorted([m[0] for m in mods], starts, side="right") - 1
    return [mods[k][2] if k >= 0 and t < mods[k][1] else None
            for k, t in zip(j, starts)]


def reduce_file(path, n_devices: int) -> SpanTrace:
    """The SpanTrace of the window in ``path`` (an ``.xplane.pb``): the
    Trace ``lib/trace.py`` reduces, with the program's host spans that
    reach into the window, the thread of the engine batches, and each
    device op's scope or program, taken in the order that reduction keeps
    the ops."""
    from jax.profiler import ProfileData
    base = trace.reduce_file(path, n_devices)
    t0, t1 = base.t0_ns, base.t1_ns
    pd = ProfileData.from_file(str(path))
    host_spans, engine_thread = [], None
    for p, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if e > t0 and s < t1:
                        host_spans.append((ev.name, s, e,
                                           dict(ev.stats), (p, k)))
                elif engine_thread is None and ev.name == BATCH_SPAN:
                    engine_thread = (p, k)
    scopes = _op_scopes(path)
    quals = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
        if dev >= n_devices:
            continue
        first, raw = len(quals), []   # unclipped starts, for the programs
        for i, line in enumerate(plane.lines):
            if line.name != OPS_LINE:
                continue
            scope = scopes.get((plane.name, i), ())
            for k, ev in enumerate(line.events):
                s = ev.start_ns
                if s + ev.duration_ns <= t0 or s >= t1:
                    continue
                raw.append(s)
                # the two readers pair events by position; an event whose
                # names differ keeps no scope and is named by its program
                md_name, sc = scope[k] if k < len(scope) else ("", None)
                quals.append(sc if md_name == ev.name else None)
        progs = _programs(plane, np.asarray(raw, np.float64))
        quals[first:] = [q or pr for q, pr in zip(quals[first:], progs)]
    out = SpanTrace(**{f.name: getattr(base, f.name)
                       for f in dataclasses.fields(trace.Trace)},
                    host_spans=host_spans, engine_thread=engine_thread)
    if host_spans or any(q and q.startswith(PROGRAM_PREFIX) for q in quals):
        labels: dict = {}
        out.label = np.asarray(
            [labels.setdefault(f"{q}/{out.names[n]}" if q else out.names[n],
                               len(labels)) for q, n in zip(quals, out.name)],
            np.int64)
        out.labels = list(labels)
    return out


def reduce_dir(trace_dir, n_devices: int) -> SpanTrace:
    """The SpanTrace of the one capture under ``trace_dir``."""
    files = sorted(pathlib.Path(trace_dir).glob(
        "plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_file(files[-1], n_devices)
