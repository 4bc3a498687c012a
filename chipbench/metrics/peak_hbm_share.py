"""peak_bytes_in_use after the window over the chip's HBM, in %."""


def read(r):
    if not r["memory_peak_bytes"] or not r["hbm_bytes"]:
        return None
    return 100.0 * r["memory_peak_bytes"] / r["hbm_bytes"]
