#!/usr/bin/env python3
"""Where a cell's idle chip time goes, by the program's own spans.

    python bench/attribute.py --workload <cell> --seed <n> --seconds <s> [--keep DIR]

Set-up and the traced window are those of ``run.py --trace 1``; the
capture is then reduced by ``lib/spantrace.py``, which reads the
program's ``pipeann.`` spans and scopes that ``lib/trace.py`` leaves
out. There is no comparison with the reference: this is a diagnosis,
not a measurement the benchmark takes. The last line of standard output
is one JSON object:

- ``breakdown``: the device ops that took most time, each named by its
  scope or program, and the idle time by innermost span;
- ``idle_s``: the idle seconds of every span;
- ``idle_share``: percent of the traced window in which the chip is idle
  and the host is in each layer of ``spantrace.HOST_LAYERS``;
- ``idle_in_batches_s`` / ``unattributed_in_batches_s``: idle seconds
  inside the engine batches, and the part no program span covers;
- ``scope_s``: device seconds under each scope or program;
- ``pq_scan_us_per_q``: device microseconds of the pre-filter's PQ scan
  (program ``jit__pq_topl``) per pre-filtered query, over the engine
  batches that ran wholly inside the capture.

With ``--keep`` the capture stays in that directory. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero.
"""
from __future__ import annotations

import argparse
import functools
import json
import shutil
import sys

from lib import serve, spantrace, spec, trace
from run import enable_compile_cache, log, require_chips

PQ_SCAN = "jit__pq_topl"


def traced_window(su: serve.Setup, seconds: float, out) -> serve.Window:
    """The cell's window under the profiler, as ``run.py --trace 1``
    runs it, with the capture written under ``out``."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return serve.run_window(su, seconds, serve.Tracer(
        functools.partial(jax.profiler.start_trace, str(out),
                          profiler_options=opts),
        jax.profiler.stop_trace, jax.profiler.TraceAnnotation))


def summary(tr: spantrace.SpanTrace, w: serve.Window) -> dict:
    """What the module's docstring lists, from the reduced capture and
    the window's answers."""
    idle = tr.idle_by_span()
    heads = sorted({n.rsplit("/", 1)[0] for n in tr.labels or ()})
    done = tr.batches_inside()
    pre = sum(1 for a in w.answers if a.ids is not None
              and a.batch in done and a.stats.mechanism == "pre")
    scan = tr.scope_s((PQ_SCAN,), within=done.values())
    return {
        "window_s": tr.window_s, "busy_s": tr.busy_s,
        "breakdown": tr.breakdown(),
        "idle_s": {n: d / 1e9 for n, d in idle.most_common()},
        "idle_share": {k: tr.idle_share(*v)
                       for k, v in spantrace.HOST_LAYERS.items()},
        "idle_in_batches_s": tr.idle_in_batches_s(),
        "unattributed_in_batches_s": idle[trace.BATCH_SPAN] / 1e9,
        "scope_s": {h: tr.scope_s((h,)) for h in heads},
        "pq_scan_us_per_q": scan / pre * 1e6 if scan > 0 and pre else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_spec(), args.workload)
    enable_compile_cache()
    if require_chips(cell.chips) is None:
        return 3
    out = args.keep or serve.WORK / "attribute"
    shutil.rmtree(out, ignore_errors=True)
    su = serve.build(cell, args.seed, log)
    try:
        w = traced_window(su, args.seconds, out)
    finally:
        serve.close(su)
    tr = spantrace.reduce_dir(out, cell.chips)
    if args.keep is None:
        shutil.rmtree(out, ignore_errors=True)
    print(json.dumps(summary(tr, w)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
