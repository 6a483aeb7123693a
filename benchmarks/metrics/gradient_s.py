"""gradient_s (s, device trace): device seconds a traced round in the
ranking gradient's program, the jitted ``_lambda_gradients_topk`` of
objective/ranking.py as the trace's ``XLA Modules`` line names it
(objective: gradient)."""

MODULE_PREFIX = "jit__lambda_gradients_topk"


def read(ctx):
    t = ctx["trace"]
    rounds = len(ctx["clocks"].get("traced_round_s", []))
    if not t or not rounds:
        return None
    spent = sum(s for name, s in t["module_s"].items()
                if name.startswith(MODULE_PREFIX))
    return spent / rounds if spent > 0 else None
