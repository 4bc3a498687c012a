"""Model FLOPs of the window's requests (every prompt and output
position once, at its context) per window second, over the bf16 peak
of the cell's chips together, in %."""


def read(r):
    if not r["peak_flops"]:
        return None
    return 100.0 * r["model_flops"] / r["window_s"] / r["peak_flops"]
