"""(token, slot) pairs routed to an expert this chip holds, per real token
and expert layer, from the ``flush`` spans' ``moe_pairs`` (the program's
device counter, summed over layers, local steps and silos) in the window.
1.0 is the even share: top-k of E experts with E/k of them held."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "moe_pairs" in a]
    if not flushes or not run["units"]:
        return None
    return sum(a["moe_pairs"] for a in flushes) / (run["units"] * flushes[0]["layers"])
