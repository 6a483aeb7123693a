"""train_rate (Mrow_rounds/s, host clock): trained rows x rounds finished in
the window over the window's whole wall time, device drained at both ends."""


def read(ctx):
    c = ctx["clocks"]
    return c["row_rounds"] / c["window_s"] / 1e6
