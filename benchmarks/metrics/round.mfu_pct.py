"""The whole step's share of the chip's published bf16 peak: matmul and
convolution FLOPs the forward and backward pass REQUIRE per real sample or
token (the benchmark's jaxpr count on the plain reference; no padded step,
nothing recomputed) times the window's samples or tokens over the traced
window's length (the trace's own clock, everything in it counted), over
chips x peak."""


def read(run):
    trace = run["trace"]
    if trace is None or "flops_per_unit" not in run:
        return None
    per_s = run["units"] / trace["window_s"]
    peak = run["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * run["flops_per_unit"] * per_s / peak
