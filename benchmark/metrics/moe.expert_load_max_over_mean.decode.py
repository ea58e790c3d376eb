"""How unevenly the window's decode steps loaded the experts: the busiest
expert's rows over the mean expert's, per layer, averaged over layers
(``moe_expert_load`` [layer][expert] at both edges of the window). 1 is
even; the grouped matmul's time follows the experts that are hit, and a
sharded deployment's the busiest one."""

LAYER = "Model step"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "serve_out_tokens_per_s"


def read(rec):
    a, b = rec.get("engine_before"), rec.get("engine_after")
    if not a or not b or not b.get("moe_expert_load"):
        return None
    ratios = []
    for before, after in zip(a["moe_expert_load"], b["moe_expert_load"]):
        rows = [y - x for x, y in zip(before, after)]
        if sum(rows) <= 0:
            return None
        ratios.append(max(rows) * len(rows) / sum(rows))
    return sum(ratios) / len(ratios)
