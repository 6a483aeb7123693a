"""dmatrix_s (s, host clock): the call that builds the binned matrix, ending
in block_until_ready on the page (data layer: sketch + binning)."""


def read(ctx):
    return ctx["clocks"].get("dmatrix_s")
