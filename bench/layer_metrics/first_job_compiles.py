"""Compile: `compilequeue.STATS["stage_compiles"]` over the first job. 0
on a warm checkout: a first job that compiles met a shape that no earlier
file of the same distribution had left an executable for."""


def read(run: dict):
    return (run["first_job"].get("cq") or {}).get("stage_compiles")
