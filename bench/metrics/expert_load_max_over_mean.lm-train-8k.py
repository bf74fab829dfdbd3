"""The held experts' largest load over their mean load, over the
window: the summed largest-held-expert load of each MoE layer and step
÷ (the summed assignments to held experts ÷ the experts held). 1 is an
even load; the step's time in the grouped matmuls follows the largest."""


def read(r):
    c = r["counters"]
    if not c.get("routed"):
        return None
    return c["max_load"] / (c["routed"] / c["held"])
