"""Real samples over the sample slots of the cohort batches placed in the
window, as the program's ``place`` spans count them (``real_samples``,
``slots``): what bucketing and padding to the cohort's largest client cost."""


def read(run):
    places = [a for n, _, _, a in run["program_spans"] if n == "place" and a.get("slots")]
    if not places:
        return None
    return 100.0 * sum(a["real_samples"] for a in places) / sum(a["slots"] for a in places)
