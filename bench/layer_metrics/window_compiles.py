"""Compile: `compilequeue.STATS["stage_compiles"]` over the window.
Anything but 0 is a fault to report: the window has to run warm."""


def read(run: dict):
    return run["window"]["cq"]["stage_compiles"]
