#!/usr/bin/env python3
"""Served filtered-search benchmark: one cell, one seed, one measured window.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix in ``BENCHMARK.json``;
``lib/spec.py`` finds their files by name. Set-up generates the corpus and
the request pool from ``--seed``, builds the index, starts the server and
runs each batch the traffic sends once through ``SearchServer.warmup``,
so that every program shape the window meets is compiled. The window then runs the
traffic's closed loop through ``SearchServer.submit`` for ``--seconds``.
Once it has closed and every answer is in, each answer is compared with
the exact reference (``lib/check.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the result
carries the cell's per-layer metrics, the device's busy and window
seconds, and a breakdown. The last line of standard output is the result
as one JSON object; the numbers compared, each beside its limit, are the
last lines of standard error and the result's last key. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result; so does a run in which a program compiled inside the
window.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import spec  # noqa: E402


def log(line: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {line}", file=sys.stderr,
          flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached, so
    only a cell's first run in a checkout compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int):
    """The first device, when JAX sees ``n`` TPU chips or more; None
    otherwise (no fallback to the CPU)."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: {e}")
        return None
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"need {n} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
        return None
    return devs[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.resolve(spec.load_spec(), args.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"cannot resolve workload {args.workload!r}: "
            f"{type(e).__name__}: {e}")
        return 2
    enable_compile_cache()
    dev = require_chips(cell.chips)
    if dev is None:
        return 3
    from lib import measure
    try:
        result, compared = measure.run_cell(cell, args.seed, args.seconds,
                                            bool(args.trace), dev, T_START,
                                            log)
    except measure.CompiledInWindow as e:
        log(f"no result: {e}")
        return 4
    for name, reading, limit in compared:
        print(f"check {name}: {reading!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
