"""Compile: seconds the first job spent tracing stage functions and
loading stored executables (`compile:trace` + `compile:aot-load` spans)."""

from harness import reading


def read(run: dict):
    return reading.span_seconds(run["first_job"]["spans"],
                                ("compile:trace", "compile:aot-load"))
