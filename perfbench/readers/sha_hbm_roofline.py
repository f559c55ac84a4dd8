"""The SHA-256 lane kernel's share of its HBM roofline: the bytes its
calls in the trace had to read and write, at the chip's HBM peak, over
the device seconds they took."""
from pbharness import kernels


def read(run):
    if run.device_trace is None:
        return None
    return kernels.hbm_roofline_pct(run.device_trace, "sha256_lanes_pallas",
                                    run.peaks)
