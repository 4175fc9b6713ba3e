"""Stage exec: rows on which a compiled `re.search` / `re.match` /
`re.sub` site could not decide and raised REGEXFALLBACK (the stage
records' `regex_fallback_rows`), as a share of the rows seen by the
stages that have such sites. None where no stage of the window carries
the counter: a program without it, or a pipeline without a compiled
regex site."""

from harness import arith


def read(run: dict):
    st = [m for m in run["window"]["stages"]
          if m.get("regex_fallback_rows") is not None]
    if not st:
        return None
    return arith.share_pct(sum(float(m["regex_fallback_rows"]) for m in st),
                           sum(float(m.get("rows_seen") or 0) for m in st))
