"""Model step (latent-attention MoE): how far the busiest expert of a
layer runs above the mean, over the prefills of the traced window: the
summed per-layer maxima of assignments to one expert
(``moe.assignments_max``) over the summed per-layer means
(``moe.assignments`` over the routed experts), from the program's
counters (ratio; 1 is an even load). A program that does not count
routing reads as nothing."""
from harness import spans


def read(run):
    total = spans.calls("moe.assignments")
    if not total:
        return None
    experts = run.cell.config["n_routed_experts"]
    return experts * spans.calls("moe.assignments_max") / total
