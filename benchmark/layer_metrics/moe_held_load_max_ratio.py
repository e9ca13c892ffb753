"""How uneven the routing got, for any family whose counts say how it
routes: the largest number of rows one HELD expert was given in any one
dispatch of the window (``moe_expert_tokens_max`` of ``SlotLoop.counters``)
over the even share of the largest dispatch the configuration makes (a
full prefill chunk or a full step, whichever has more tokens: tokens x
experts a token / PUBLISHED experts, both from ``benchmark/counts/
<family>.py``'s ``experts_per_token`` and ``published_experts``, so a
family needs no config key of another's).  1 is even; the expert layer's
first padded tier holds ``min(4 x even, even + 64)`` rows an expert, its
wide tier 256.  None where the program keeps no such counter or the
family's counts have no such functions."""
import importlib

from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c, cfg = _slot_loop.stats(ctx), ctx["config"]
    if "moe_expert_tokens_max" not in c:
        return None
    try:
        counts = importlib.import_module(f"benchmark.counts.{ctx['family']}")
        share = counts.experts_per_token(cfg) / counts.published_experts(cfg)
    except (ImportError, AttributeError):
        return None
    sv = cfg["serve"]
    return c["moe_expert_tokens_max"] \
        / (max(int(sv["prefill_chunk"]), int(sv["slots"])) * share)
