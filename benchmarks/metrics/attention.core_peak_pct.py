"""The latent-attention core's share of the chip's published bf16 peak: the
FLOPs its two score products and its value product REQUIRE over the causal
half, forward and backward (``core_flops``), for every document trained in
the traced window, per second of that window, over chips x peak. A share of
the peak over the WHOLE window, as ``moe.expert_peak_pct`` is: what the core
needs against everything the window took, not the kernels' own roofline
(``lib/trace.py`` keeps the ten largest ops, so a reader cannot sum the
kernels' device time yet; ``tools/anatomy.py`` can). ``round.mfu_pct``
counts the same products full T x T, as the reference writes them. ``None``
where no flush span carries the sites' widths (a model without latent
attention, or a program from before they existed)."""


def core_flops(length: int, heads: int, qk_width: int, v_width: int) -> float:
    """One layer's attention core on one document, forward and backward:
    per head the scores (qk_width deep) and the values (v_width wide) over
    the length^2 / 2 live (query, key) pairs at 2 FLOPs a multiply-add,
    once forward and 2.5 times backward (five products of the forward's two:
    the scores again, dP, dV, dQ and dK)."""
    return 3.5 * 2 * (length * length / 2) * heads * (qk_width + v_width)


def read(run):
    trace = run["trace"]
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "attn_qk_width" in a]
    if trace is None or not flushes:
        return None
    a = flushes[0]
    documents = run["units"] / a["attn_length"]
    flops = documents * a["attn_layers"] * core_flops(
        a["attn_length"], a["attn_heads"], a["attn_qk_width"], a["attn_v_width"])
    return 100.0 * flops / trace["window_s"] / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
