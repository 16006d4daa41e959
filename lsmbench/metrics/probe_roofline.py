"""Share (%) of the HBM roofline the fused Bloom probe reaches: the
least bytes the window's probes needed (``roofline.probe_bytes``) over
the HBM peak, divided by the device time of the probe programs
launched inside the front door's get calls."""
from ..roofline import peaks, share
from ..trace import device_seconds


def read(run):
    if run.trace is None:
        return None
    secs = device_seconds(run.trace, "bloom_probe", span="get")
    if secs <= 0:
        return None
    return share(run.probe_bytes, secs,
                 peaks(run.device_kind)["hbm_bytes_per_s"])
