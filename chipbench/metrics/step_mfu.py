"""Model FLOPs of the window's requests (every prompt and output
position once, at its context) per window second, over the chip's
bf16 peak, in %."""


def read(r):
    if not r["peak_flops"]:
        return None
    return 100.0 * r["model_flops"] / r["window_s"] / r["peak_flops"]
