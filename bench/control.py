#!/usr/bin/env python3
"""Readings of the control and of planted faults, for setting the limits
of ``limits/<cell>.json``. The benchmark's own runs never run this.

    python bench/control.py --workload <cell> --seeds 1,2,3

For each seed it generates the cell's corpus and request pool and puts in
the program's place: the reference computed in bfloat16 (the control),
and the float32 reference with a fault planted (no answer at all, half
of each batch left out, the first id of each answer altered). Each is
judged by ``lib/check.py`` at the cell's own size; one JSON line per seed
and kind.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import check, reference, spec  # noqa: E402
from lib.corpus import make_corpus  # noqa: E402
from lib.traffic import make_pool  # noqa: E402


def answers_of(ids: np.ndarray, dists: np.ndarray) -> list:
    return [(r, ids[r][ids[r] >= 0], dists[r][ids[r] >= 0])
            for r in range(ids.shape[0])]


def readings(cell: spec.Cell, seed: int) -> dict:
    cfg, t = cell.config, cell.traffic
    k = int(cfg["search"]["k"])
    c = make_corpus(cfg["corpus"], seed, int(t["pool"]))
    pool = make_pool(t, c, seed)
    rows = np.arange(len(pool))
    ids, dists = reference.exact_topk(c, pool, rows, k)
    low_ids, low_d = reference.exact_topk(c, pool, rows, k, "bfloat16")
    half = ids.copy()
    half[::2] = -1
    altered = ids.copy()
    altered[:, 0] = np.where(ids[:, 0] >= 0, (ids[:, 0] + 1) % c.n, -1)
    kinds = {
        "control_bfloat16": answers_of(low_ids, low_d),
        "fault_state_unchanged": answers_of(np.full_like(ids, -1), dists),
        "fault_half_left_out": answers_of(half, dists),
        "fault_answer_altered": answers_of(altered, dists),
    }
    return {kind: check.judge(c, pool, ans, 0, k)
            for kind, ans in kinds.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = spec.resolve(spec.load_spec(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind, r in readings(cell, seed).items():
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "kind": kind, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
