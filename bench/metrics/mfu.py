"""``mfu.<cells>``: the least time the window's completed work needs at the
chip's peaks (CIMA plane products at the int8 peak, float work outside the
CIMA at the bf16 peak), as a share of the window, in %."""


def read(name, r):
    return 100.0 * r.result["work"].compute_seconds(r.peak) / r.window_s
