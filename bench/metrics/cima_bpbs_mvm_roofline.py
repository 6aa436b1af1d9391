"""``cima_bpbs_mvm_roofline.<cells>``: the least time the window's kernel
calls need (their plane products at the int8 peak, or their bytes at the
HBM bandwidth, whichever binds; ``bench/work.py``) over the device time of
the ``cima_bpbs_mvm`` events in the trace, in %.  Nothing when the trace
holds no such event."""


def read(name, r):
    kernel_s = r.summary.matched_s.get("cima_bpbs_mvm", 0.0)
    if kernel_s <= 0.0:
        return None
    return 100.0 * r.result["kernel_work"].least_seconds(r.peak) / kernel_s
