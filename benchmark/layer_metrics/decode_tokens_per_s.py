"""New tokens of the requests that resolved in the window over ALL of the
window's seconds.  Answers come whole and the server replies per packed
batch, so this moves in steps of a few per cent and swings with where the
window falls against the ring's sessions (PERF.md section 2): a per-layer
reading, not a number to judge a change by."""


def compute(ctx):
    w = ctx["window"]
    if not w.get("tokens") or not w.get("seconds"):
        return None
    return w["tokens"] / w["seconds"]
