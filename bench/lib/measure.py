"""One run of a cell: set-up, window, the comparison, and the metrics."""
from __future__ import annotations

import dataclasses
import functools
import json
import shutil
import time

import numpy as np

from lib import check, serve, spec

TRACE_DIR = serve.WORK / "trace"


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader (``metrics/<name>.py``) reads."""
    cell: spec.Cell
    setup: serve.Setup
    window: serve.Window
    answered: list           # answers that came back from the window's
                             # start to the end of the drain
    trace: object = None     # lib.trace.Trace of the window, when traced
    peaks: dict = None       # the device's row of peaks.json


def end_to_end(w: serve.Window, seconds: float, readings: dict,
               setup_s: float) -> dict:
    """The end-to-end metrics, from the host clock over the window:
    queries answered at full service (rung 0) per second of the window,
    each credited with the share of its engine batch's run that lies
    inside the window, so that a batch cut by an edge counts for the part
    of it the window saw and a stall anywhere in the window shows; the
    95th percentile of submit-to-result latency of the queries answered
    at full service inside the window; mean recall@10 over every answer;
    set-up seconds."""
    share = {b.number: w.share_inside(b) for b in w.batches}
    ok = [a for a in w.answers if a.status == "ok"]
    lat = np.array([a.t_done - a.t_sub for a in ok
                    if w.t0 <= a.t_done <= w.t_end])
    return {
        "qps": sum(share.get(a.batch, 0.0) for a in ok) / seconds,
        "p95_ms": float(np.percentile(lat, 95)) * 1e3 if lat.size else None,
        "recall10": 1.0 - readings["recall_short"],
        "setup_s": setup_s,
    }


class CompiledInWindow(RuntimeError):
    """A program compiled inside the measured window: set-up did not warm
    every shape the window ran, so the run measures nothing."""


def peaks_of(kind: str) -> dict:
    with open(spec.BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             dev, t_start: float, log) -> tuple:
    import jax
    peaks = peaks_of(dev.device_kind) if traced else None
    su = serve.build(cell, seed, log)
    tracer = None
    try:
        if traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            tracer = serve.Tracer(
                functools.partial(jax.profiler.start_trace, str(TRACE_DIR),
                                  profiler_options=opts),
                jax.profiler.stop_trace, jax.profiler.TraceAnnotation)
        w = serve.run_window(su, seconds, tracer)
        setup_s = w.t0 - t_start
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:cell.chips]]
    finally:
        serve.close(su)
    log(f"window: {len(w.answers)} requests, {len(w.batches)} engine "
        f"batches, {w.compiles} compiles inside, drained "
        f"{w.t_drained - w.t_end:.2f}s after the close")
    if w.compiles:
        raise CompiledInWindow(f"{w.compiles} program(s) compiled inside "
                               f"the window")
    answered = [a for a in w.answers if a.ids is not None]
    t0 = time.perf_counter()
    readings = check.judge(su.corpus, su.pool,
                           [(a.row, a.ids, a.dists) for a in answered],
                           sum(a.status != "ok" for a in w.answers),
                           int(cell.config["search"]["k"]))
    correct, compared = check.verdict(readings, cell.limits)
    log(f"reference and comparison: {time.perf_counter() - t0:.1f}s")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(max(mem))}
    result = {"correct": bool(correct), "attempted": len(w.answers),
              "failed": sum(a.status != "ok" for a in w.answers)}
    if traced:
        from lib import trace as trace_mod
        tr = trace_mod.reduce_dir(TRACE_DIR, cell.chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run = Run(cell, su, w, [a for a in answered if a.t_done >= w.t0],
                  tr, peaks)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = tr.breakdown()
    else:
        e2e = end_to_end(w, seconds, readings, setup_s)
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end
                             if e2e.get(m["name"]) is not None}
        result["device"] = device
    result["check"] = {name: {"value": r, "limit": lim}
                       for name, r, lim in compared}
    return result, compared
