"""Set-up time: process start to the window's start, when the first
request is due (imports, device start, data, build, warm-up, compile or
cache load). Host clock."""


def read(run):
    return run.setup_s
