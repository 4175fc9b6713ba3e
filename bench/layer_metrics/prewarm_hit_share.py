"""Compile: of the speculative compiles the precompile driver submitted
(`compilequeue.STATS["prewarm_submitted"]`), the share whose executable a
dispatch's lookup then took (`prewarm_used`, once for each fingerprint);
the first job and the window together. A compile that no dispatch ever
finds is the fault this watches (PERF.md, fault 1)."""

from harness import arith


def read(run: dict):
    cqs = (run["first_job"]["cq"], run["window"]["cq"])
    if any("prewarm_submitted" not in cq for cq in cqs):
        return None
    submitted = sum(cq["prewarm_submitted"] for cq in cqs)
    return arith.share_pct(sum(cq["prewarm_used"] for cq in cqs), submitted)
