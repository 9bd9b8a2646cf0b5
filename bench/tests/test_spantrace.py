"""CPU tests of the reduction by the program's own spans and scopes
(``bench/lib/spantrace.py``) and of ``bench/attribute.py``, which runs a
cell's window under the profiler and reduces it so: the attribution of
idle gaps to the innermost span, the op names by scope or program, the
metadata reader, and a traced rehearsal of one cell on the CPU."""
from __future__ import annotations

import gzip
import json
import pathlib
import sys
import types

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))

import attribute  # noqa: E402
from lib import serve, spantrace  # noqa: E402
from lib import trace as trace_mod  # noqa: E402
from test_perfbench import tiny_cell  # noqa: E402

DATA = BENCH / "tests" / "data"


def _unzip(name: str, tmp_path) -> pathlib.Path:
    path = tmp_path / name.removesuffix(".gz")
    with gzip.open(DATA / name) as f:
        path.write_bytes(f.read())
    return path


def _spans_trace():
    """Idle gaps [0, 100), [200, 600), [700, 1000) of a window [0, 1000);
    one engine batch [50, 900) on thread "w", the server's worker, with
    nested program spans there, and admission pricing on thread "c". The
    op [100, 200) runs inside a ``while`` of the same extent."""
    P = "pipeann."
    return spantrace.SpanTrace(
        start=np.array([100., 600., 100.]), dur=np.array([100., 100., 100.]),
        device=np.array([0, 0, 0]), name=np.array([0, 1, 2]),
        names=["fusion.1", "fusion.2", "while.1"],
        spans=[("bench.engine_batch", 50, 900, {"batch": 0})],
        t0_ns=0, t1_ns=1000, n_devices=1,
        host_spans=[(P + "server.execute", 40, 850, {"batch": 0}, "w"),
                    (P + "index.prepare", 50, 120, {}, "w"),
                    (P + "engine.group", 120, 800, {"mech": "pre"}, "w"),
                    (P + "prefilter.dispatch", 130, 300, {}, "w"),
                    (P + "hops.readback", 400, 500, {}, "w"),
                    (P + "server.price", 0, 30, {}, "c"),
                    (P + "server.price", 900, 1000, {}, "c")],
        engine_thread="w",
        labels=["pipeann.hop.merge/fusion.1", "jit_run_hops/fusion.2",
                "pipeann.hop.merge/while.1"],
        label=np.array([0, 1, 2]))


def test_idle_goes_to_the_innermost_program_span():
    tr = _spans_trace()
    idle = tr.idle_by_span()
    assert dict(idle) == {
        "pipeann.server.price": 30 + 100,      # [0,30), [900,1000): caller
        "between engine batches": 10,           # [30,40): no span anywhere
        "pipeann.server.execute": 10 + 50,      # [40,50), [800,850)
        "pipeann.index.prepare": 50,            # [50,100)
        "pipeann.prefilter.dispatch": 100,      # [200,300)
        "pipeann.engine.group": 100 + 100 + 100,
        "pipeann.hops.readback": 100,           # [400,500)
        "bench.engine_batch": 50,               # [850,900): the batch alone
    }
    assert sum(idle.values()) == sum(e - s for s, e in tr.gaps()) == 800
    assert min(idle.values()) > 0
    # [50,100), [200,600), [700,900)
    assert tr.idle_in_batches_s() == pytest.approx(650e-9)
    bd = tr.breakdown()
    assert [n for n, _ in bd["device_ops"]] == [
        "pipeann.hop.merge/fusion.1", "jit_run_hops/fusion.2"]
    assert dict(bd["idle_gaps"])["pipeann.engine.group"] == \
        pytest.approx(300e-9)
    # the while holds the op it encloses: counted once, as its body
    assert tr.scope_s(("pipeann.hop.merge",)) == pytest.approx(100e-9)
    assert tr.scope_s(("pipeann.hop.merge",), within=[(300, 900)]) == 0.0
    # an op without a scope is found by its program
    assert tr.scope_s(("pipeann.hop.merge", "jit_run_hops")) == \
        pytest.approx(200e-9)


def test_summary_reads_host_layers_and_the_scan():
    tr = _spans_trace()
    tr.labels = ["jit__pq_topl/fusion.1", "jit__pq_topl/fusion.2",
                 "jit__pq_topl/while.1"]
    stats = types.SimpleNamespace(mechanism="pre")
    w = types.SimpleNamespace(answers=[
        serve.Answer(r, 0.0, 1.0, "ok", ids=np.zeros(1), stats=stats,
                     batch=0) for r in (0, 1)])
    got = attribute.summary(tr, w)
    # shares of a 1000 ns window
    assert got["idle_share"] == {"prefilter": pytest.approx(10.0),
                                 "hop_driver": pytest.approx(10.0),
                                 "assembly": pytest.approx(5.0),
                                 "server": pytest.approx(13.0)}
    assert got["unattributed_in_batches_s"] == pytest.approx(50e-9)
    assert got["idle_in_batches_s"] == pytest.approx(650e-9)
    assert got["scope_s"] == {"jit__pq_topl": pytest.approx(200e-9)}
    # the scan's device time per pre-filtered query of the batches inside
    # the window, by its program; the scan's while is not counted again
    assert got["pq_scan_us_per_q"] == pytest.approx(200e-9 / 2 * 1e6)
    json.dumps(got)


def test_trace_without_program_spans_reduces_as_before(tmp_path):
    """The recorded chip trace of ``expected.json`` holds no program span
    or scope: the reduction gives what ``lib/trace.py`` gives, op for op,
    and reports no host layer."""
    path = _unzip("search.xplane.pb.gz", tmp_path)
    base, tr = trace_mod.reduce_file(path, 1), spantrace.reduce_file(path, 1)
    assert not tr.host_spans and tr.label is None
    assert tr.breakdown() == base.breakdown()
    assert tr.busy_s == base.busy_s and tr.window_s == base.window_s
    assert all(tr.idle_share(*v) is None
               for v in spantrace.HOST_LAYERS.values())


def test_recorded_chip_trace_names_each_op_by_program(tmp_path):
    """The metadata reader and the program lookup on the recorded chip
    trace: one scope slot per device op, and every op of the window runs
    inside a program of the ``XLA Modules`` line."""
    from jax.profiler import ProfileData
    path = _unzip("search.xplane.pb.gz", tmp_path)
    want = json.loads((DATA / "expected.json").read_text())
    scopes = spantrace._op_scopes(path)
    ((plane_name, _), slots), = scopes.items()
    assert plane_name == "/device:TPU:0" and len(slots) >= want["n_ops"]
    tr = trace_mod.reduce_file(path, 1)
    plane = next(p for p in ProfileData.from_file(str(path)).planes
                 if p.name == plane_name)
    progs = spantrace._programs(plane, tr.start)
    assert all(p and p.startswith("jit_") for p in progs)
    assert spantrace._innermost_scope(
        "jit(run_hops)/while/body/pipeann.hop.merge/jit(sort)/sort") == \
        "pipeann.hop.merge"
    assert spantrace._innermost_scope("jit(init_search)/gather:") is None
    # the metadata reader and ``ProfileData`` see the events in one order
    ops = next(ln for ln in plane.lines if ln.name == trace_mod.OPS_LINE)
    assert [n for n, _ in slots] == [ev.name for ev in ops.events]


def test_recorded_chip_trace_with_program_spans(tmp_path):
    """A trace with the program's spans and scopes, recorded on one TPU v5
    lite (``data/spans_expected.json`` says how): the reduction and the
    host layers' shares give what they gave there, each kernel sits under
    the scope of its stage, and no top op keeps a bare name."""
    want = json.loads((DATA / "spans_expected.json").read_text())
    tr = spantrace.reduce_file(_unzip("spans.xplane.pb.gz", tmp_path), 1)
    assert tr.start.size == want["n_ops"]
    assert len(tr.host_spans) == want["n_host_spans"]
    assert tr.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert sorted(tr.batches_inside()) == want["batches_inside"]
    bd = tr.breakdown()
    for key in ("device_ops", "idle_gaps"):
        assert bd[key] == [[n, pytest.approx(v, rel=1e-9)]
                           for n, v in want["breakdown"][key]]
    assert all("/" in n for n, _ in bd["device_ops"])
    idle = {k: v / 1e9 for k, v in tr.idle_by_span().items()}
    assert idle == {k: pytest.approx(v, rel=1e-9)
                    for k, v in want["idle_s"].items()}
    assert {h: tr.scope_s((h,)) for h in want["scope_s"]} == {
        h: pytest.approx(v, rel=1e-9) for h, v in want["scope_s"].items()}
    shares = {k: tr.idle_share(*v) for k, v in spantrace.HOST_LAYERS.items()}
    assert shares == {k: pytest.approx(v, rel=1e-9)
                      for k, v in want["idle_share"].items()}
    # a known pairing: the kernels inside the hop step carry its scopes
    for label in tr.labels:
        head, _, op = label.rpartition("/")
        if op.startswith("hop_fused"):
            assert head == "pipeann.hop.fused_pass"
        if op.startswith("or_scatter"):
            assert head in ("pipeann.hop.slot_select", "jit_init_search")


@pytest.mark.parametrize("paired", [True, False])
def test_scopes_are_taken_only_from_the_same_event(tmp_path, monkeypatch,
                                                   paired):
    """An op takes the scope of the metadata slot at its position only
    where the slot names the same event; otherwise it takes none, and
    this trace, with no program spans, reduces with bare names as before,
    never under another op's scope."""
    path = _unzip("search.xplane.pb.gz", tmp_path)
    real = spantrace._op_scopes(path)
    fake = {k: [(n if paired else "%other", "pipeann.hop.fake")
                for n, _ in slots] for k, slots in real.items()}
    monkeypatch.setattr(spantrace, "_op_scopes", lambda _: fake)
    tr = spantrace.reduce_file(path, 1)
    if paired:
        assert {n.rsplit("/", 1)[0] for n in tr.labels} == \
            {"pipeann.hop.fake"}
    else:
        assert tr.labels is None and tr.label is None


@pytest.fixture(scope="module")
def tiny():
    su = serve.build(tiny_cell(), 2 ** 31 + 5, log=lambda line: None)
    yield su
    serve.close(su)


def test_traced_rehearsal_reads_the_program_spans(tiny, tmp_path):
    """The window under the profiler on the CPU, as ``attribute.py`` runs
    it: the reduction finds the program's spans on the engine batch's
    thread, and the summary reads them. The CPU capture has no TPU plane,
    so every nanosecond of the window is idle and the attribution covers
    all of it."""
    w = attribute.traced_window(tiny, 1.0, tmp_path)
    tr = spantrace.reduce_dir(tmp_path, 1)
    assert tr.engine_thread is not None and tr.batches_inside()
    names = {n for n, *_ in tr.host_spans}
    assert {"pipeann.server.execute", "pipeann.engine.group",
            "pipeann.hops.dispatch", "pipeann.index.assemble"} <= names
    idle = tr.idle_by_span()
    assert sum(idle.values()) == pytest.approx(tr.window_s * 1e9, rel=1e-9)
    assert min(idle.values()) >= 0
    got = attribute.summary(tr, w)["idle_share"]
    assert got["prefilter"] == 0.0     # all routed in-filter
    assert all(got[k] > 0 for k in ("hop_driver", "assembly", "server"))
    assert sum(got.values()) < 100.0
