"""Work the algorithm needs, for the roofline share of a kernel.

The work is counted from the algorithm, not from the implementation, so
that an implementation that does less (narrower codes, no one-hot ADC)
raises the share and padded rows count as waste.
"""
from __future__ import annotations

PQ_CENTROIDS = 256       # K: centroids per sub-quantizer (uint8 codes)


def hop_fused_work(hops: int, beam: int, r: int, r_dense: int, pq_m: int,
                   n_fields: int) -> tuple:
    """(operations, bytes) of ``hops`` query-hops of the fused candidate
    pass: every hop scores ``beam * (r + r_dense)`` candidates. Each
    candidate needs its ``pq_m`` one-byte codes, its 4-byte bloom word and
    its ``n_fields`` one-byte bucket codes, and ``pq_m`` table additions;
    each query-hop reads its float32 distance table (``pq_m * 256``
    entries) once."""
    cand = hops * beam * (r + r_dense)
    ops = cand * pq_m
    nbytes = cand * (pq_m + 4 + n_fields) + hops * pq_m * PQ_CENTROIDS * 4
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of operations at the vector unit's
    rate and bytes at HBM bandwidth, and which of the two bounds it."""
    t_ops = ops / peaks["vpu_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "vpu") if t_ops >= t_mem else (t_mem, "hbm")
