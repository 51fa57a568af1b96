"""Share of the chosen (token, slot) pairs, over all experts, that the
router's selection bias put there: pairs in the top-k of the scores plus the
bias that the top-k of the scores alone does not hold (``moe_bias_moved`` over
``moe_calls`` x tokens a call x top-k, i.e. all chosen pairs; the ``flush``
spans in the window carry the counter, and the chosen pairs are the window's
real tokens x top-k x expert layers). 0 would be a bias that chooses nothing;
the configuration draws it so that about a tenth of the pairs move. ``None``
where no flush span carries the counter (a router without a selection bias,
or a program from before it existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"]
               if n == "flush" and "moe_bias_moved" in a and "top_k" in a]
    if not flushes or not run["units"]:
        return None
    chosen = run["units"] * flushes[0]["top_k"] * flushes[0]["expert_layers"]
    return 100.0 * sum(a["moe_bias_moved"] for a in flushes) / chosen
