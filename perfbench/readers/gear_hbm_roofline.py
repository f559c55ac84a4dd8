"""The gear kernel's share of its HBM roofline, as for the SHA kernel."""
from pbharness import kernels


def read(run):
    if run.device_trace is None:
        return None
    return kernels.hbm_roofline_pct(run.device_trace, "gear_bitmap_flat",
                                    run.peaks)
