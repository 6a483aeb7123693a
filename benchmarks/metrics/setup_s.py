"""setup_s (s, host clock): process start to the window's opening: data,
host-to-device copy, sketch and binning, compile or cache load, warm rounds."""


def read(ctx):
    return ctx["clocks"]["setup_s"]
