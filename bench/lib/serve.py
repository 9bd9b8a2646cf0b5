"""Set-up and measured window of one cell, through the program's served
path: ``SearchServer.submit`` -> ``Session`` batching -> ``engine.execute``
(router, pipelined hop loop or pre-filter scan, exact verification).

This is the only module of the benchmark that imports the program.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import threading
import time

import numpy as np

from lib.corpus import Corpus, make_corpus
from lib.spec import BENCH, ROOT, Cell
from lib.traffic import Pool, caller_batches, make_pool

WORK = BENCH / ".work"           # scratch inside the checkout
WAIT_S = 60.0                    # how long past the window an answer may take


def _program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def to_expr(f: dict):
    """A traffic filter as the program's filter DSL."""
    from repro.api import Num, Tag
    parts = [] if f["tag"] is None else [Tag("tag") == int(f["tag"])]
    parts += [Num(field).between(lo, hi) for field, lo, hi in f["ranges"]]
    expr = parts[0]
    for p in parts[1:]:
        expr = expr & p
    return expr


@dataclasses.dataclass
class Setup:
    cell: Cell
    corpus: Corpus
    pool: Pool
    index: object
    server: object
    requests: list
    build_s: float


def pool_batches(cell: Cell, pool_size: int) -> list:
    """Every batch of pool rows the closed loop sends, once each."""
    t = cell.traffic
    batch, callers = int(t["batch"]), int(t["callers"])
    n = -(-pool_size // batch)
    out = []
    for c in range(callers):
        gen = caller_batches(pool_size, callers, batch, c)
        out += [next(gen) for _ in range(c, n, callers)]
    return out


def build(cell: Cell, seed: int, log=print) -> Setup:
    """Corpus and pool from ``seed``, the index, the server, and a warm-up
    that runs each batch the closed loop sends once, through
    ``SearchServer.warmup``: the window then meets no program shape that
    set-up has not run."""
    _program()
    from repro.api import (Index, IndexConfig, Schema, SearchConfig,
                           SearchRequest)
    from repro.serve.server import SearchServer, ServerConfig
    cfg, t = cell.config, cell.traffic
    t0 = time.perf_counter()
    corpus = make_corpus(cfg["corpus"], seed, int(t["pool"]))
    pool = make_pool(t, corpus, seed)
    meta = corpus.metadata()
    log(f"corpus: N={corpus.n} d={corpus.vectors.shape[1]}, pool "
        f"{len(pool)}, {time.perf_counter() - t0:.1f}s")
    ic, sc = cfg["index"], cfg["search"]
    t0 = time.perf_counter()
    index = Index.build(
        corpus.vectors, meta,
        IndexConfig(seed=seed % (2 ** 31 - 1), **ic),
        schema=Schema(tags=("tag",), nums=tuple(corpus.values)),
        defaults=SearchConfig(**sc))
    import jax
    jax.block_until_ready((index.engine.store, index.engine.codes,
                           index.engine.mem))
    build_s = time.perf_counter() - t0
    log(f"build: {build_s:.1f}s")
    requests = [SearchRequest(query=corpus.queries[i],
                              filter=to_expr(pool.filters[i]),
                              k=sc["k"], l=sc["l"])
                for i in range(len(pool))]
    server = SearchServer(index, ServerConfig(**cfg["server"]))
    t0 = time.perf_counter()
    for rows in pool_batches(cell, len(pool)):
        server.warmup([requests[r] for r in rows], ladder=False, rungs=())
    log(f"warm-up: {time.perf_counter() - t0:.1f}s")
    return Setup(cell, corpus, pool, index, server, requests, build_s)


@dataclasses.dataclass
class Answer:
    row: int                 # pool row
    t_sub: float
    t_done: float
    status: str              # ok | degraded | refused | error | lost
    ids: np.ndarray = None
    dists: np.ndarray = None
    stats: object = None     # the program's RequestStats
    batch: int = -1          # the engine batch that answered it


@dataclasses.dataclass
class Batch:
    """One engine batch the server ran in the window."""
    number: int
    t0: float
    t1: float
    requests: int
    graph_hops: int          # hops of its requests routed through the graph


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    t_drained: float
    answers: list
    batches: list            # Batch, in the order the server ran them
    compiles: int            # programs compiled inside the window

    def share_inside(self, b: Batch) -> float:
        """The share of engine batch ``b``'s run that lies inside the
        window."""
        inside = min(b.t1, self.t_end) - max(b.t0, self.t0)
        return max(0.0, inside) / max(b.t1 - b.t0, 1e-9)


class _CompileCounter:
    def __init__(self):
        import jax
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@dataclasses.dataclass
class Tracer:
    """Captures a profiler trace from the window's start until
    ``batches`` engine batches have started and ended inside it, or the
    window closes; the host span ``bench.window`` marks the capture."""
    start: object            # jax.profiler.start_trace, bound to a dir
    stop: object             # jax.profiler.stop_trace
    annotate: object         # jax.profiler.TraceAnnotation
    batches: int = 3


def run_window(su: Setup, seconds: float, tracer: Tracer | None = None
               ) -> Window:
    """The closed loop: ``callers`` threads, each with one batch of
    ``batch`` requests outstanding, submitting its next batch when all of
    the last one has returned. The loop starts in set-up, one caller
    after another, so that each engine batch is one caller's batch; the
    window opens once every caller has had its first batch back, so that
    it sees the loop in its steady state."""
    import jax
    from repro.api import ServeError
    t = su.cell.traffic
    callers, batch = int(t["callers"]), int(t["batch"])
    index, server = su.index, su.server
    answers, batches = [], []
    lock = threading.Lock()
    plain = index.search_batch
    row_of = {id(r): i for i, r in enumerate(su.requests)}
    batch_of = {}                 # pool row -> engine batch that ran it
    submitted = [threading.Event() for _ in range(callers)]
    first_back = [threading.Event() for _ in range(callers)]
    clock = {"t_end": float("inf")}

    def counted(reqs, *a, **kw):
        with lock:
            number = len(batches)
            batches.append(None)
            for r in reqs:
                batch_of[row_of.get(id(r))] = number
        b0, out = time.perf_counter(), []
        try:
            if tracer is None:
                out = plain(reqs, *a, **kw)
            else:
                with tracer.annotate("bench.engine_batch", batch=number):
                    out = plain(reqs, *a, **kw)
        finally:
            graph = [r.stats.hops for r in out
                     if r.stats.mechanism in ("in", "post")]
            batches[number] = Batch(number, b0, time.perf_counter(),
                                    len(reqs), sum(graph))
        return out

    def caller(c: int):
        mine = []
        if c:
            submitted[c - 1].wait(seconds + 2 * WAIT_S)
        for rows in caller_batches(len(su.pool), callers, batch, c):
            if time.perf_counter() >= clock["t_end"]:
                break
            handles = []
            for r in rows:
                ts = time.perf_counter()
                try:
                    handles.append((r, ts, server.submit(su.requests[r])))
                except ServeError:
                    mine.append(Answer(r, ts, ts, "refused"))
            submitted[c].set()
            for r, ts, h in handles:
                left = min(clock["t_end"] - time.perf_counter(), seconds)
                wait = max(1.0, left + WAIT_S)
                try:
                    res = h.result(timeout=wait)
                except TimeoutError:
                    mine.append(Answer(r, ts, np.inf, "lost"))
                    continue
                except Exception:            # the request's own failure
                    mine.append(Answer(r, ts, time.perf_counter(), "error"))
                    continue
                with lock:
                    number = batch_of.get(r, -1)
                mine.append(Answer(
                    r, ts, time.perf_counter(),
                    "ok" if h.rung == "full" else "degraded",
                    res.ids, res.dists, res.stats, number))
            first_back[c].set()
        with lock:
            answers.extend(mine)

    def trace():
        tracer.start()
        n0 = len(batches)
        with tracer.annotate("bench.window"):
            while time.perf_counter() < clock["t_end"]:
                with lock:
                    done = [b for b in batches[n0:] if b is not None]
                if len(done) >= tracer.batches + 1:
                    break
                time.sleep(0.05)
        tracer.stop()

    counter = _CompileCounter()
    index.search_batch = counted
    threads = [threading.Thread(target=caller, args=(c,), daemon=True,
                                name=f"bench-caller-{c}")
               for c in range(callers)]
    try:
        for th in threads:
            th.start()
        for ev in first_back:
            ev.wait(seconds + 2 * WAIT_S)
        t0 = time.perf_counter()
        clock["t_end"] = t0 + seconds
        counter.on = True
        jax.config.update("jax_log_compiles", True)
        if tracer is not None:
            threads.append(threading.Thread(target=trace, daemon=True,
                                             name="bench-tracer"))
            threads[-1].start()
        for th in threads:
            th.join(seconds + 2 * WAIT_S)
    finally:
        counter.on = False
        jax.config.update("jax_log_compiles", False)
        index.search_batch = plain
    t_drained = time.perf_counter()
    if any(th.is_alive() for th in threads):
        raise RuntimeError("a caller is still waiting after the drain")
    return Window(t0, clock["t_end"], t_drained, answers,
                  [b for b in batches if b is not None], counter.n)


def close(su: Setup) -> None:
    """Stop the server and drop the program's state, so that the
    reference that follows has the device to itself."""
    su.server.stop()
    su.server = su.index = su.requests = None
    gc.collect()
