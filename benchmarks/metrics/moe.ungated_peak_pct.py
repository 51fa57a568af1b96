"""The UNGATED expert products' share of the chip's published bf16 peak:
``moe.expert_peak_pct``'s reading for experts of two matrices
(``relu(x W_up)**2 W_down``): FLOPs that the pairs routed to held experts
REQUIRE (``pair_flops``) per second of the traced window, over chips x peak.
``round.mfu_pct`` counts matmuls in the reference's jaxpr and skips its
grouped products, so in a cell with routed experts this is the part of the
step it leaves out. ``None`` unless the flush spans say that a held pair
runs two products forward (``expert_products`` 2): a gated layer's pairs are
``moe.expert_peak_pct``'s, whose ``pair_flops`` reckons three."""


def pair_flops(hidden: int, expert_width: int) -> float:
    """One (token, slot) pair through one ungated expert, forward and
    backward: two products of hidden x expert_width (up, down) at 2 FLOPs a
    multiply-add, once forward and twice backward (towards the activations
    and towards the weights)."""
    return 2 * 2 * hidden * expert_width * 3


def read(run):
    trace = run["trace"]
    flushes = [a for n, _, _, a in run["program_spans"]
               if n == "flush" and "moe_pairs" in a and a.get("expert_products") == 2]
    if trace is None or not flushes:
        return None
    flops = sum(a["moe_pairs"] * pair_flops(a["hidden"], a["expert_width"]) for a in flushes)
    return 100.0 * flops / trace["window_s"] / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
