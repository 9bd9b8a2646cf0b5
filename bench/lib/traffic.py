"""One general generator of filtered-search traffic, driven by a traffic
file's parameters.

A traffic file names its public source, and fixes the loop (closed:
``callers`` each with one batch of ``batch`` requests outstanding), the
pool of distinct requests the callers cycle through, and the filter
shapes with their shares, each with the law its selectivity follows:

- ``{"dist": "loguniform", "lo": a, "hi": b}``: a target share of the
  records, log-uniform in [a, b]; a tag is the one whose coverage is
  nearest its part of the target;
- ``{"dist": "tag_popularity"}`` (shape ``tag`` only): the tag drawn with
  probability proportional to the number of records that hold it, so
  that queries follow the corpus's own popularity law.

Every seed gets the same set of shapes and strata (quantiles of the law,
stratified over the pool), in another order, and its own queries, tags
and range positions, so a seed changes the inputs and not the amount of
work.

A filter is plain data: ``{"tag": int | None, "ranges": [[field, lo, hi],
...]}`` meaning "holds ``tag``" AND ``lo <= field < hi`` for every range;
the reference evaluates it on the raw corpus, independent of the index.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from lib.corpus import Corpus

SHAPES = ("tag", "range", "tag_and_ranges")
LAWS = ("loguniform", "tag_popularity")


@dataclasses.dataclass
class Pool:
    """The distinct requests of one seed: request ``i`` is row ``i`` of
    the corpus's queries under ``filters[i]`` (``{"tag", "ranges"}``)."""
    filters: list

    def __len__(self) -> int:
        return len(self.filters)


def _tag_near(cover_frac: np.ndarray, frac: float, rng) -> int:
    """A tag whose coverage is nearest ``frac`` (random among ties)."""
    gap = np.abs(np.log(np.maximum(cover_frac, 1e-9)) - np.log(frac))
    best = np.flatnonzero(gap <= gap.min() * 1.0001 + 1e-12)
    return int(rng.choice(best))


def _tag_at(cover: np.ndarray, q: float) -> int:
    """The tag at quantile ``q`` of the popularity law: tags from the most
    to the least held, each weighted by the records that hold it."""
    order = np.argsort(-cover, kind="stable")
    cdf = np.cumsum(cover[order], dtype=np.float64)
    return int(order[min(np.searchsorted(cdf, q * cdf[-1]), cover.size - 1)])


def _range(sorted_vals: np.ndarray, frac: float, rng) -> tuple:
    """[lo, hi) holding about ``frac`` of the records, at a random place;
    both ends are values of the field, so float32 comparisons are exact."""
    n = sorted_vals.size
    width = max(1, int(round(frac * n)))
    start = int(rng.integers(0, n - width))
    return float(sorted_vals[start]), float(sorted_vals[start + width])


def _check(t: dict) -> None:
    if t["loop"] != "closed":
        raise ValueError("the generator knows the closed loop only")
    for s in t["shapes"]:
        law = s["selectivity"]["dist"]
        if s["shape"] not in SHAPES or law not in LAWS:
            raise ValueError(f"unknown filter shape or law {s!r}")
        if law == "tag_popularity" and s["shape"] != "tag":
            raise ValueError("tag_popularity draws single tags only")


def make_pool(t: dict, c: Corpus, seed: int) -> Pool:
    """The ``t["pool"]`` distinct requests of one seed (``t`` is a traffic
    file); ``c`` holds at least as many queries."""
    _check(t)
    rng = np.random.default_rng([seed, 1])
    size = int(t["pool"])
    share = np.array([float(s["share"]) for s in t["shapes"]])
    counts = np.floor(share / share.sum() * size).astype(int)
    counts[: size - counts.sum()] += 1
    shape_of, quant = [], []
    for spec, m in zip(t["shapes"], counts):
        quant.extend((np.arange(m) + 0.5) / m)          # stratified
        shape_of.extend([spec] * m)
    order = rng.permutation(size)
    shape_of = [shape_of[i] for i in order]
    quant = np.asarray(quant)[order]

    cover = c.tag_cover()
    sorted_vals = {f: np.sort(v) for f, v in c.values.items()}
    filters = []
    for spec, q in zip(shape_of, quant):
        law = spec["selectivity"]
        if law["dist"] == "tag_popularity":
            filters.append({"tag": _tag_at(cover, q), "ranges": []})
            continue
        lo, hi = float(law["lo"]), float(law["hi"])
        s = lo * (hi / lo) ** q
        kind = spec["shape"]
        if kind == "tag":
            f = {"tag": _tag_near(cover / c.n, s, rng), "ranges": []}
        elif kind == "range":
            field = spec["fields"][0]
            f = {"tag": None,
                 "ranges": [[field, *_range(sorted_vals[field], s, rng)]]}
        else:
            fields = spec["fields"]
            # the tag and each range at an equal share of the selectivity
            part = s ** (1.0 / (len(fields) + 1))
            f = {"tag": _tag_near(cover / c.n, part, rng),
                 "ranges": [[fl, *_range(sorted_vals[fl], part, rng)]
                            for fl in fields]}
        filters.append(f)
    return Pool(filters)


def caller_batches(pool_size: int, callers: int, batch: int, caller: int):
    """Endless pool indices of one caller's batches: caller ``c`` takes
    batches ``c, c + callers, ...`` of the pool, wrapping around."""
    j = caller
    n_batches = -(-pool_size // batch)
    while True:
        b = j % n_batches
        yield [(b * batch + i) % pool_size for i in range(batch)]
        j += callers
