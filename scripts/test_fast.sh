#!/usr/bin/env bash
# Inner-loop test run: only tests marked `fast`, skipping the
# Vamana-build-heavy suites, plus a tiny end-to-end smoke of the build
# benchmark (catches benchmark-script bitrot without paying the full
# 12K-corpus run). The tier-1 gate stays the full
# `PYTHONPATH=src python -m pytest -x -q`.
set -euo pipefail
cd "$(dirname "$0")/.."
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_build --smoke
# the search smoke doubles as the seeded fault-injection smoke: the
# default --fault-plan (10% page-fault rate, seed 7) re-runs every mode
# under injection and asserts the degraded-mode recall floor
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_search --smoke --store disk
# serving-tier smoke: degrade-rung calibration + a tiny Poisson
# open-loop sweep through the threaded SearchServer (no floors)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m benchmarks.bench_serve --smoke
# multi-device mesh leg: the dist suite launches its own subprocesses
# with fake CPU devices, but setting the flag here too keeps any
# in-process jax usage on the same 4-device topology the tests assume
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -q -m dist
# light chaos tests (deterministic fault hash, injector, latency model)
# are marked fast+chaos and ride the -m fast run below; the full chaos
# property suite is `pytest -m chaos` (tier-1 runs it unmarked too)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} exec python -m pytest -q -m fast "$@"
