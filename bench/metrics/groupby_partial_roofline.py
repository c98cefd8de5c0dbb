"""Kernels: the groupby partial-aggregation program
(``kernels.ops._segment_reduce_multi_prog``, around ``segment_reduce``) as a
share of its roofline: the bytes its calls must move
(``kernel_bytes.groupby_partial``) over the chip's HBM bandwidth, against the
device time of its programs in the trace.  None where the window made no
such call or the trace shows no such program."""
from bench.metrics import kernel_bytes
from bench.peaks import peaks

PROGRAM = "_segment_reduce_multi_prog"


def read(w):
    calls = w.kernels.get(PROGRAM, [])
    t = w.device.program_s(PROGRAM) if w.device is not None else 0.0
    if not calls or t <= 0:
        return None
    least = sum(kernel_bytes.groupby_partial(c) for c in calls) / peaks(w.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / t
