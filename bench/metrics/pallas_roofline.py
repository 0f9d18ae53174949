"""The Pallas kernels' share of their roofline: for every batch served
in the traced window and every SC/PC/FC layer, the least time the chip
could take, max(2*B*P*S*F / int8 peak, needed bytes / HBM bandwidth)
from unpadded shapes (opcount.kernel_least_time_s), summed and divided
by the kernels' device time, in %."""
import opcount


def value(run):
    if run.trace is None or not run.trace.n_pallas:
        return None
    least = 0.0
    for size, t0, t1 in run.batches:
        if run.t_start <= t1 <= run.t_end:
            for g in run.geoms:
                if g.on_mxu:
                    least += max(opcount.kernel_least_time_s(
                        g, size, run.peaks["int8_ops_per_s"],
                        run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / run.trace.pallas_s
