"""Program spans (``repro.utils.spans``): the served path records one
``pipeann.<layer>.<stage>`` span per host stage, nested as the code
runs them, into a profiler capture, and nothing outside one."""
import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import (Index, IndexConfig, Num, SearchConfig, SearchRequest,
                       Tag)
from repro.serve.server import SearchServer, ServerConfig

pytestmark = pytest.mark.fast

N = 900

# span -> the span it runs inside (None: a thread's outermost)
NESTING = {
    "server.price": None,
    "server.fill_wait": None,
    "server.cut": None,
    "server.execute": None,
    "server.complete": "server.execute",
    "index.prepare": "server.execute",
    "index.assemble": "server.execute",
    "engine.route": "server.execute",
    "engine.group": "server.execute",
    "engine.collect": "engine.group",
    "prefilter.superset": "engine.group",
    "prefilter.dispatch": "engine.group",
    "prefilter.readback": "engine.group",
    "hops.init": "engine.group",
    "hops.dispatch": "engine.group",
    "hops.readback": "engine.group",
    "hops.compact": "engine.group",
    "hops.finalize": "engine.group",
}


@pytest.fixture(scope="module")
def index():
    rng = np.random.default_rng(11)
    vectors = rng.normal(0, 1, (N, 24)).astype(np.float32)
    cats = [sorted(set(int(x) for x in rng.integers(0, 12,
                                                    rng.integers(1, 4))))
            for _ in range(N)]
    values = rng.uniform(0, 100, N).astype(np.float32)
    meta = [{"cat": c, "value": float(v)} for c, v in zip(cats, values)]
    idx = Index.build(vectors, meta,
                      IndexConfig(r=12, r_dense=64, l_build=24, pq_m=8),
                      defaults=SearchConfig(k=10, l=32, max_hops=128))
    return idx, vectors


def _requests(vectors):
    # three through the graph (in-filtering pinned), three pre-filtered
    # (a 1% range)
    return ([SearchRequest(query=vectors[i], filter=Tag("cat") == 1,
                           policy="spec_in") for i in range(3)]
            + [SearchRequest(query=vectors[i],
                             filter=Num("value").between(40, 41))
               for i in range(3, 6)])


def _program_spans(trace_dir) -> list:
    """(name, start, end, stats, thread) of every ``pipeann.`` span on
    the host plane of the one capture under ``trace_dir``."""
    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            out += [(ev.name[len("pipeann."):], ev.start_ns,
                     ev.start_ns + ev.duration_ns, dict(ev.stats), k)
                    for ev in line.events if ev.name.startswith("pipeann.")]
    return out


def _parent(span, spans):
    """The innermost span on the same thread that encloses ``span``."""
    name, s, e, _, th = span
    outer = [x for x in spans if x is not span and x[4] == th
             and x[1] <= s and e <= x[2] and (x[1], -x[2]) < (s, -e)]
    return max(outer, key=lambda x: (x[1], -x[2]), default=None)


def test_served_batch_records_every_stage_nested(index, tmp_path):
    idx, vectors = index
    reqs = _requests(vectors)
    mechs = [r.stats.mechanism for r in idx.search_batch(reqs)]
    assert "pre" in mechs and "in" in mechs
    with SearchServer(idx, ServerConfig(max_batch=len(reqs),
                                        max_delay_s=5.0)) as srv:
        srv.warmup(reqs)
        with jax.profiler.trace(str(tmp_path)):
            handles = [srv.submit(r) for r in reqs]
            for h in handles:
                h.result(timeout=120)
            # the worker's refit closes ``server.complete`` just after
            # the handles resolve
            while srv.stats().completed < len(reqs):
                time.sleep(0.01)
            time.sleep(0.2)
    spans = _program_spans(tmp_path)
    assert {s[0] for s in spans} == set(NESTING)
    for sp in spans:
        parent = _parent(sp, spans)
        want = NESTING[sp[0]]
        if want is None:
            assert parent is None, (sp, parent)
        else:
            assert parent is not None and parent[0] == want, (sp, parent)
    # admission pricing runs on the caller's thread, the rest on the
    # server's worker
    worker = {s[4] for s in spans if s[0] == "server.execute"}
    assert len(worker) == 1
    assert {s[4] for s in spans if s[0] == "server.price"}.isdisjoint(worker)
    assert all(s[4] in worker for s in spans if s[0] != "server.price")
    (execute,) = [s for s in spans if s[0] == "server.execute"]
    assert execute[3] == {"batch": 0, "size": len(reqs), "rung": "full"}
    groups = {s[3]["mech"]: s[3]["width"] for s in spans
              if s[0] == "engine.group"}
    assert groups == {"pre": mechs.count("pre"), "in": mechs.count("in")}
    assert sum(s[0] == "prefilter.dispatch" for s in spans) == \
        mechs.count("pre")
    assert all({"active", "bucket", "hop"} <= set(s[3]) for s in spans
               if s[0] == "hops.dispatch")


def test_no_span_outside_a_capture(index, tmp_path):
    idx, vectors = index
    reqs = _requests(vectors)
    idx.search_batch(reqs)
    with jax.profiler.trace(str(tmp_path)):
        pass
    idx.search_batch(reqs)
    assert _program_spans(tmp_path) == []
