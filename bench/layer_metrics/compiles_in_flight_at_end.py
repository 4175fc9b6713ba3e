"""Compile: compiles still running as the window closes: those begun
(`compilequeue.STATS["compile_starts"]`) less those ended, well
(`stage_compiles`) or not (`compile_failures`), over the first job and the
window together. An end that falls between the two (one `plan_stages`
call, well under a second) is not seen and reads as one in flight."""


def read(run: dict):
    cqs = (run["first_job"]["cq"], run["window"]["cq"])
    if any("compile_starts" not in cq for cq in cqs):
        return None
    return sum(cq["compile_starts"] - cq["stage_compiles"]
               - cq["compile_failures"] for cq in cqs)
