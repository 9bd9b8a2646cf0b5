"""Share of its roofline that ``hop_fused`` reaches, over the engine
batches that ran wholly inside the traced window: the least time of the
work their graph-routed queries needed (``lib/roofline.py``, at the peaks
of ``peaks.json``), over the kernel's device time in those batches."""
from lib import roofline


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.batches_inside()
    hops = sum(b.graph_hops for b in run.window.batches if b.number in spans)
    t = run.trace.kernel_s("hop_fused", within=spans.values())
    if t <= 0 or hops == 0:
        return None
    ix = run.cell.config["index"]
    ops, nbytes = roofline.hop_fused_work(
        hops, beam=1, r=ix["r"], r_dense=ix["r_dense"], pq_m=ix["pq_m"],
        n_fields=len(run.cell.config["corpus"]["numeric_fields"]))
    least, _ = roofline.least_time_s(ops, nbytes, run.peaks)
    return 100.0 * least / t
