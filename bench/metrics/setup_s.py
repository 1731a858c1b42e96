"""Set-up seconds: data, index build, server start and compilation."""


def read(run):
    return run["setup_s"]
