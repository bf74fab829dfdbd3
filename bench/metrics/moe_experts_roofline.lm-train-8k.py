"""The held experts' grouped matmuls in the LM train step: the least
time of the window's routed-token operations and held-expert weight
and row bytes (``work_lm.held_experts``) at the chip's peaks ÷ the
device time under the ``moe/experts`` scope."""
from harness import work, work_lm


def read(r):
    c, t = r["counters"], r["trace"]
    spent = ((t or {}).get("layer_s") or {}).get("moe/experts")
    if not spent or not c.get("steps"):
        return None
    w = work_lm.held_experts(r["config"], c["routed"] / c["steps"],
                             c["moe_layers"])
    return 100.0 * c["steps"] * work.least_seconds([w], r["peaks"]) / spent
