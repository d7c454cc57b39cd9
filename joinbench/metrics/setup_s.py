"""setup_s: seconds from process start to the window's start (loading,
generation, compilation or cache loads, warm-up)."""


def read(run):
    return run.result.setup_s
