"""round_max_s (s, host clock): the longest of the window's untraced rounds,
one round being the gap between two after_iteration calls (booster loop)."""


def read(ctx):
    return ctx["clocks"].get("round_max_s")
