"""Share of the HBM roofline reached by the embedding-bag pooling kernel,
%: useful bytes of the traced flushes' served requests (each valid index
reads one row, its index and its weight; each real table writes one pooled
row; counted from the batch, never from what the kernel DMAs) over the
kernel's device time summed over chips x the HBM bandwidth.  Pooling is
bound by bytes, so the byte roof is its roofline."""
import numpy as np

import counts

# the kernel's device events.  A trace names each op by its HLO instruction;
# XLA names the Pallas pooling kernel's custom call after the jitted wrapper
# of kernels/ops.py that calls it ("embedding_bag_stacked_op.8"), and no
# other op of the step carries that name
KERNEL_NAMES = ("embedding_bag_stacked_op", "embedding_bag_rows_op")


def read(run):
    red, w = run.trace, run.window
    if red is None or w.traced is None:
        return None
    ns = sum(t for name, t in red.op_ns.items()
             if any(k in name for k in KERNEL_NAMES))
    if ns <= 0:
        return None
    served = np.isin(w.flush_of, list(w.traced))
    useful = float(counts.pooling_bytes(run.cell["config"],
                                        run.valid[served]).sum())
    return 100.0 * useful / (ns * 1e-9 * run.peaks["hbm_bytes_per_s"])
