"""Share of the traced slice's device-busy seconds spent inside the prefix
cache's two data movers: the ``kv_push_block`` program (a cached block
written into a joining row's columns) and the ``kv_pull_block`` program (a
new block read out of an activated row), by their names on the ``XLA
Modules`` line.  None without a capture, or where neither ran in the slice
(the parent, or a loop without the cache)."""

PROGRAMS = ("jit_push", "jit_pull")


def compute(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    ran = [tr["programs"][p] for p in PROGRAMS if p in tr.get("programs", {})]
    if not ran:
        return None
    return 100.0 * sum(p["total_s"] for p in ran) / tr["busy_s"]
