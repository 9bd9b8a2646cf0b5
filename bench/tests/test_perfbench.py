"""CPU tests of the benchmark under ``bench/``: the trace reduction, the
roofline work function, the lookup of cells and metrics by name, and a
tiny rehearsal of one cell's set-up and window through the harness's own
functions, clean, under the control, and with the timed path broken.
Nothing here looks for a chip, and nothing prints a device metric."""
from __future__ import annotations

import copy
import gzip
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import check, measure, reference, roofline, serve, spec  # noqa: E402
from lib import traffic  # noqa: E402
from lib import trace as trace_mod  # noqa: E402

CELL = "paper192_hbm.label"
DATA = BENCH / "tests" / "data"


# -- trace reduction ---------------------------------------------------------

def test_trace_busy_gaps_kernels_and_labels():
    # ops: fusion [100, 150), hop_fused [120, 200) inside it, fusion
    # [400, 500), a while loop around [100, 500), and an op on a chip
    # the cell does not use
    names = ["fusion.1", "hop_fused.7", "fusion.3", "while.2"]
    tr = trace_mod.Trace(
        start=np.array([100., 120., 400., 100., 10.]),
        dur=np.array([50., 80., 100., 400., 5.]),
        device=np.array([0, 0, 0, 0, 1]),
        name=np.array([0, 1, 2, 3, 0]), names=names,
        spans=[("bench.engine_batch", 50, 300, {"batch": 0}),
               ("bench.engine_batch", 350, 1200, {"batch": 1})],
        t0_ns=0, t1_ns=1000, n_devices=1)
    assert tr.busy_intervals(0).tolist() == [[100, 500]]
    assert tr.busy_s == pytest.approx(400e-9)
    assert tr.window_s == pytest.approx(1000e-9)
    assert tr.kernel_s("hop_fused") == pytest.approx(80e-9)
    assert tr.kernel_s("hop_fused", within=[(300, 600)]) == 0.0
    assert tr.batches_inside() == {0: (50, 300)}
    assert tr.gaps() == [(0.0, 100.0), (500.0, 1000.0)]
    bd = tr.breakdown()
    assert [n for n, _ in bd["device_ops"]] == ["fusion.3", "hop_fused.7",
                                                "fusion.1"]
    idle = dict(bd["idle_gaps"])
    # idle [0,100) is half inside batch 0; [500,1000) inside batch 1
    assert idle["bench.engine_batch"] == pytest.approx(50e-9 + 500e-9)
    assert idle["between engine batches"] == pytest.approx(50e-9)


def test_trace_reduction_on_recorded_chip_trace(tmp_path):
    """A trace recorded on one TPU v5 lite (``data/expected.json`` says
    how): the reduction finds the device plane, the window and batch
    spans, and the ``hop_fused`` kernel, and reads what it read there."""
    want = json.loads((DATA / "expected.json").read_text())
    path = tmp_path / "search.xplane.pb"
    with gzip.open(DATA / "search.xplane.pb.gz") as f:
        path.write_bytes(f.read())
    tr = trace_mod.reduce_file(path, 1)
    assert tr.start.size == want["n_ops"]
    assert tr.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert tr.kernel_s("hop_fused") == pytest.approx(want["hop_fused_s"],
                                                     rel=1e-9)
    assert sorted(tr.batches_inside()) == want["batches_inside"]
    assert 0 < tr.kernel_s("hop_fused") < tr.busy_s < tr.window_s
    assert tr.breakdown()["device_ops"] == [
        [n, pytest.approx(v, rel=1e-9)]
        for n, v in want["breakdown"]["device_ops"]]


# -- roofline work -----------------------------------------------------------

def test_hop_fused_work_hand_count():
    # one query-hop at the paper's widths: 96 + 1100 candidates, each with
    # 32 code bytes, a 4-byte bloom word and 2 bucket bytes; one 32 x 256
    # float32 distance table; 32 table additions per candidate
    ops, nbytes = roofline.hop_fused_work(1, beam=1, r=96, r_dense=1100,
                                          pq_m=32, n_fields=2)
    assert ops == 1196 * 32 == 38272
    assert nbytes == 1196 * 38 + 32 * 256 * 4 == 78216
    ops4, nbytes4 = roofline.hop_fused_work(10, beam=4, r=96, r_dense=1100,
                                            pq_m=32, n_fields=2)
    assert ops4 == 40 * ops and nbytes4 == 40 * 1196 * 38 + 10 * 32768
    peaks = {"vpu_ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    assert roofline.least_time_s(ops, nbytes, peaks) == (
        pytest.approx(78216 / 1e11), "hbm")


# -- the rate over the window ------------------------------------------------

def test_qps_credits_rung0_answers_by_their_batch_inside_the_window():
    B, A = serve.Batch, serve.Answer
    # window [10, 20): batch 0 straddles the opening, batch 1 lies inside,
    # batch 2 straddles the close, batch 3 runs after it
    batches = [B(0, 8.0, 12.0, 2, 0), B(1, 12.0, 16.0, 2, 0),
               B(2, 16.0, 24.0, 2, 0), B(3, 24.0, 26.0, 2, 0)]
    answers = [A(0, 7.0, 12.0, "ok", batch=0), A(1, 7.0, 12.0, "ok", batch=0),
               A(2, 11.0, 16.0, "ok", batch=1),
               A(3, 11.0, 16.0, "degraded", batch=1),
               A(4, 15.0, 24.0, "ok", batch=2), A(5, 15.0, 24.0, "ok", batch=2),
               A(6, 23.0, 26.0, "ok", batch=3), A(7, 9.0, 9.0, "refused")]
    w = serve.Window(10.0, 20.0, 27.0, answers, batches, 0)
    e2e = measure.end_to_end(w, 10.0, {"recall_short": 0.25}, 3.0)
    # 2 x 0.5 (batch 0) + 1 (batch 1, rung 0 only) + 2 x 0.5 (batch 2)
    assert e2e["qps"] == pytest.approx(3.0 / 10.0)
    assert e2e["p95_ms"] == pytest.approx(5000.0)
    assert e2e["recall10"] == 0.75 and e2e["setup_s"] == 3.0


# -- traffic -------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["label", "range"])
def test_every_seed_gets_the_same_strata(mix):
    from lib.corpus import make_corpus
    t = json.loads((BENCH / "traffic" / f"{mix}.json").read_text())
    t = dict(t, pool=128)
    cfg = dict(spec.resolve(spec.load_spec(), CELL).config["corpus"],
               n=4000, dim=8)
    seen = []
    for seed in (2 ** 31 + 11, 2 ** 31 + 12):
        c = make_corpus(cfg, seed, 128)
        pool = traffic.make_pool(t, c, seed)
        tag, lo, hi = reference.filter_arrays(c, pool, np.arange(128))
        vals = np.stack(list(c.values.values()), axis=1)
        ok = ((tag[:, None] < 0) | np.any(
            reference.padded_tags(c)[None] == tag[:, None, None], -1))
        ok &= np.all((vals[None] >= lo[:, None]) & (vals[None] < hi[:, None]),
                     -1)
        seen.append(np.sort(ok.mean(1)))
    # the same selectivities in another order, to the corpus's sampling
    # (a stratum on the boundary of two tags may take either)
    assert np.median(np.abs(seen[0] - seen[1])) < 0.005
    assert abs(seen[0].mean() - seen[1].mean()) < 0.01
    assert seen[0][0] < 0.05 and seen[0][-1] > 0.3


def test_warm_up_covers_every_batch_the_loop_sends():
    cell = tiny_cell()
    t = cell.traffic
    got = serve.pool_batches(cell, t["pool"])
    sent = set()
    for c in range(t["callers"]):
        gen = traffic.caller_batches(t["pool"], t["callers"], t["batch"], c)
        sent |= {tuple(next(gen)) for _ in range(50)}
    assert sorted(map(tuple, got)) == sorted(sent)


# -- cells and metrics by name -------------------------------------------------

def test_every_cell_and_metric_resolves():
    s = spec.load_spec()
    for w in s["workloads"]:
        cell = spec.resolve(s, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        assert set(cell.limits) == set(check.NUMBERS)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
            moves = {e["name"] for e in cell.end_to_end}
            assert m["moves"] in moves


def test_new_metric_and_cell_are_files_plus_entries(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(BENCH / d, bench / d)
    (bench / "metrics" / "answered_count.py").write_text(
        "def read(run):\n    return len(run.answered)\n")
    s = spec.load_spec()
    s["per_layer"].append({"name": "answered_count", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "server", "moves": "qps"})
    shutil.copy(bench / "traffic" / "label.json",
                bench / "traffic" / "label_and.json")
    s["workloads"].append({"name": "paper192_hbm.label_and",
                           "config": "paper192_hbm", "traffic": "label_and",
                           "chips": 1, "why": "a test cell"})
    shutil.copy(bench / "limits" / f"{CELL}.json",
                bench / "limits" / "paper192_hbm.label_and.json")
    cell = spec.resolve(s, "paper192_hbm.label_and", bench=bench)
    assert [m["name"] for m in cell.per_layer][-1] == "answered_count"
    read = spec.metric_reader("answered_count", bench=bench)
    assert read(type("R", (), {"answered": [1, 2, 3]})()) == 3


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        CELL, "--seed", str(2 ** 31 + 7), "--seconds", "1",
                        "--trace", "0"], cwd=BENCH.parent, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# -- a tiny rehearsal of one cell on the CPU -----------------------------------

def tiny_cell() -> spec.Cell:
    """The label cell at a size the CPU builds in seconds; the router is
    pinned to in-filtering, which a corpus this small would otherwise
    bypass, so that the hop loop carries the answers, and the tags are
    common ones, which in-filtering finds at this size."""
    cell = spec.resolve(spec.load_spec(), CELL)
    cfg = copy.deepcopy(cell.config)
    cfg["corpus"].update(n=1500, dim=32)
    cfg["index"].update(r=12, r_dense=48, l_build=24, pq_m=8)
    cfg["search"].update(l=96, policy="spec_in")
    cfg["server"]["max_batch"] = 8
    cell.config = cfg
    cell.traffic = dict(cell.traffic, pool=48, batch=8, callers=2, shapes=[
        {"shape": "tag", "share": 1,
         "selectivity": {"dist": "loguniform", "lo": 0.1, "hi": 0.5}}])
    return cell


@pytest.fixture(scope="module")
def tiny():
    su = serve.build(tiny_cell(), 2 ** 31 + 3, log=lambda line: None)
    yield su
    serve.close(su)


def _judge(su, w):
    answered = [a for a in w.answers if a.ids is not None]
    readings = check.judge(su.corpus, su.pool,
                           [(a.row, a.ids, a.dists) for a in answered],
                           sum(a.status != "ok" for a in w.answers),
                           su.cell.config["search"]["k"])
    return check.verdict(readings, su.cell.limits)


def test_rehearsal_window_is_correct(tiny):
    w = serve.run_window(tiny, 1.0)
    assert w.compiles == 0
    assert w.answers and all(a.status == "ok" for a in w.answers)
    assert all(a.stats.mechanism == "in" for a in w.answers)
    assert sum(b.requests for b in w.batches) >= len(w.answers)
    # each engine batch is one caller's batch of pool rows
    batch = tiny.cell.traffic["batch"]
    for b in w.batches:
        rows = sorted(a.row for a in w.answers if a.batch == b.number)
        assert not rows or (len(rows) == batch
                            and rows[0] % batch == 0
                            and rows == list(range(rows[0],
                                                   rows[0] + batch)))
    correct, rows = _judge(tiny, w)
    assert correct, rows
    e2e = measure.end_to_end(w, 1.0, {"recall_short": 0.0}, 0.0)
    assert e2e["qps"] > 0 and e2e["p95_ms"] > 0


def test_control_is_not_correct(tiny):
    """The reference in bfloat16, put in the program's place."""
    import control
    rows = np.arange(len(tiny.pool))
    ids, dists = reference.exact_topk(tiny.corpus, tiny.pool, rows, 10,
                                      precision="bfloat16")
    readings = check.judge(tiny.corpus, tiny.pool,
                           control.answers_of(ids, dists), 0, 10)
    correct, _ = check.verdict(readings, tiny.cell.limits)
    assert not correct
    assert readings["dist_err"] > tiny.cell.limits["dist_err"]


def _frozen_hops(store, codes, mem, ctx, st, n_hops, params, **kw):
    import jax.numpy as jnp
    return st, jnp.zeros(st.active.shape, jnp.int8)


def _half_left_out(execute):
    def run(self, queries, selectors, scfgs):
        ids, dists, stats = execute(self, queries, selectors, scfgs)
        for i in range(0, len(ids), 2):
            ids[i] = np.full_like(ids[i], -1)
            dists[i] = np.full_like(dists[i], np.inf)
        return ids, dists, stats
    return run


def _answer_altered(execute):
    def run(self, queries, selectors, scfgs):
        ids, dists, stats = execute(self, queries, selectors, scfgs)
        for i in range(len(ids)):
            ids[i] = np.asarray(ids[i]).copy()
            if ids[i][0] >= 0:
                ids[i][0] = (ids[i][0] + 1) % self.n
        return ids, dists, stats
    return run


def _planted_error(execute, q0):
    def run(self, queries, selectors, scfgs):
        if np.any(np.all(np.asarray(queries) == q0, axis=1)):
            raise RuntimeError("planted failure")
        return execute(self, queries, selectors, scfgs)
    return run


@pytest.mark.parametrize("fault", ["error", "degraded", "refused"])
def test_requests_not_served_in_full_are_not_correct(tiny, monkeypatch,
                                                     fault):
    """A request that fails, is served at a degrade rung, or is refused
    at admission makes ``correct`` false, and ``qps`` leaves it out."""
    from repro.api import Overloaded
    from repro.core.engine import FilteredANNEngine
    from repro.serve.server import SearchServer
    if fault == "error":
        monkeypatch.setattr(FilteredANNEngine, "execute", _planted_error(
            FilteredANNEngine.execute, tiny.corpus.queries[0]))
    elif fault == "degraded":
        monkeypatch.setattr(SearchServer, "_pick_rung_locked",
                            lambda self, now: 1)
    else:
        submit, calls = SearchServer.submit, [0]

        def refuse_some(self, request):
            calls[0] += 1
            if calls[0] % 5 == 0:
                raise Overloaded("planted refusal", retry_after_s=0.0)
            return submit(self, request)
        monkeypatch.setattr(SearchServer, "submit", refuse_some)
    w = serve.run_window(tiny, 1.0)
    assert any(a.status != "ok" for a in w.answers)
    correct, rows = _judge(tiny, w)
    assert not correct, rows
    assert dict((n, r) for n, r, _ in rows)["unanswered"] > 0
    if fault == "degraded":
        e2e = measure.end_to_end(w, 1.0, {"recall_short": 0.0}, 0.0)
        assert e2e["qps"] == 0 and e2e["p95_ms"] is None


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(tiny, monkeypatch, fault):
    from repro.core import search
    from repro.core.engine import FilteredANNEngine
    if fault == "state_unchanged":
        monkeypatch.setattr(search, "run_hops", _frozen_hops)
    else:
        wrap = {"half_left_out": _half_left_out,
                "answer_altered": _answer_altered}[fault]
        monkeypatch.setattr(FilteredANNEngine, "execute",
                            wrap(FilteredANNEngine.execute))
    w = serve.run_window(tiny, 1.0)
    correct, rows = _judge(tiny, w)
    assert not correct, rows
