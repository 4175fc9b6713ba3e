"""The weblogs pipeline (Tuplex, SIGMOD'21, section 6.1; upstream
`benchmarks/logs/runtuplex.py`, regex mode): the pipeline asked of the
program, its plain CPython reference and the comparison that decides
`correct`.

Each Common Log Format line is parsed by one anchored `re.search` with
nine groups into a dict row (the empty row where the pattern does not
match); a user's home page `/~name...` is anonymized by `re.sub` with ten
letters drawn by `random.choice`; an inner join with the block list on the
address keeps the requests from block-listed addresses; seven columns are
selected. The reference below runs the same UDFs row by row over `csv`
rows and imports nothing of the program. The program draws its letters
from its own generator, not CPython's sequence, so the comparison masks
the ten drawn letters and holds them to being ten upper-case letters that
fall evenly over the alphabet.
"""

from __future__ import annotations

import math
import random
import re
import string

FACT = "logs"
SIDE = ("bad_ips",)
OUTPUT_COLS = ["ip", "date", "method", "endpoint", "protocol",
               "response_code", "content_size"]
_EP = OUTPUT_COLS.index("endpoint")
_HOME = re.compile("^/~[^/]+")
_UPPER = frozenset(string.ascii_uppercase)


def ParseWithRegex(logline):
    match = re.search(
        r'^(\S+) (\S+) (\S+) \[([\w:/]+\s[+\-]\d{4})\] "(\S+) (\S+)\s*(\S*)'
        r'\s*" (\d{3}) (\S+)', logline)
    if match:
        return {"ip": match.group(1),
                "client_id": match.group(2),
                "user_id": match.group(3),
                "date": match.group(4),
                "method": match.group(5),
                "endpoint": match.group(6),
                "protocol": match.group(7),
                "response_code": int(match.group(8)),
                "content_size": 0 if match.group(9) == "-"
                else int(match.group(9))}
    else:
        return {"ip": "",
                "client_id": "",
                "user_id": "",
                "date": "",
                "method": "",
                "endpoint": "",
                "protocol": "",
                "response_code": -1,
                "content_size": -1}


def randomize_udf(x):
    return re.sub("^/~[^/]+", "/~" + "".join(
        [random.choice(list(string.ascii_uppercase)) for t in range(10)]), x)


def build(ctx, paths: dict):
    """The source's script over `ctx.csv(logs)`, not yet collected."""
    df = ctx.csv(paths["logs"])
    df = df.map(lambda x: ParseWithRegex(x["logline"]))
    df = df.withColumn("endpoint", lambda x: randomize_udf(x["endpoint"]))
    bad_ip_df = ctx.csv(paths["bad_ips"])
    df = df.join(bad_ip_df, "ip", "BadIPs")
    return df.selectColumns(OUTPUT_COLS)


# ---------------------------------------------------------------------------
# the plain reference: `csv` rows in, tuples out
# ---------------------------------------------------------------------------

def reference_partial(columns: list, rows: list, side: dict,
                      control=False) -> list:
    """The script in plain CPython over one chunk. A row whose UDF raises
    is dropped, as the program drops a row no tier can resolve. The
    control leaves the home pages as they are."""
    at = columns.index("logline")
    bad = side["bad_ips"].get("_set")
    if bad is None:
        i = side["bad_ips"]["columns"].index("BadIPs")
        bad = side["bad_ips"]["_set"] = {r[i] for r in side["bad_ips"]["rows"]}
    out = []
    for rec in rows:
        try:
            x = ParseWithRegex(rec[at])
            if not control:
                x["endpoint"] = randomize_udf(x["endpoint"])
        except Exception:       # the UDF's own error: the row is dropped
            continue
        if x["ip"] in bad:
            out.append(tuple(x[c] for c in OUTPUT_COLS))
    return out


def reference_merge(partials: list, control=False) -> list:
    """Chunks in input order: the join keeps its probe side's order."""
    return [row for part in partials for row in part]


def anonymized(row) -> bool:
    """Whether `randomize_udf` replaced a match in the reference's row:
    its endpoint matches the pattern after the replacement exactly where
    it did before (the ten letters are no '/')."""
    return _HOME.match(row[_EP]) is not None


def _typed(row, masked: bool) -> tuple:
    """The row's cells with their types; a masked endpoint without the ten
    drawn letters (its first two characters and what follows the tenth
    letter, and its length)."""
    cells = [(type(v), v) for v in row]
    if masked:
        ep = row[_EP]
        cells[_EP] = (type(ep), len(ep), ep[:2], ep[12:])
    return tuple(cells)


def compare(got: list, want: list, limits: dict) -> list:
    """(name, value, limit), rows in input order. Where rows are missing
    (or extra), the longer answer's rows that the other lacks are stepped
    over, as many as the answers differ by. A row differs if a cell differs
    by value or by type, the ten letters drawn for an anonymized home page
    masked. Of those letters: `letters_not_upper` counts rows whose ten are
    not all of A-Z, `letter_share_off` is the widest gap between a
    letter's share and 1/26 over six standard deviations of a fair draw's
    share at that count (under 1 for a fair draw)."""
    got = [tuple(r) for r in got]
    spare = abs(len(got) - len(want))
    gi = wi = differ = not_upper = 0
    counts = dict.fromkeys(string.ascii_uppercase, 0)
    while gi < len(got) and wi < len(want):
        row = tuple(want[wi])
        masked = anonymized(row)
        key = _typed(row, masked)
        if _typed(got[gi], masked) != key and spare:
            if len(got) > len(want):
                gi += 1
            else:
                wi += 1
            spare -= 1
            continue
        g = got[gi]
        gi, wi = gi + 1, wi + 1
        if _typed(g, masked) != key:
            differ += 1
            continue
        if masked:
            letters = g[_EP][2:12]
            if len(letters) != 10 or not set(letters) <= _UPPER:
                not_upper += 1
            for c in letters:
                if c in counts:
                    counts[c] += 1
    total = sum(counts.values())
    off = 0.0
    if total:
        p = 1.0 / 26
        sd = math.sqrt(p * (1 - p) / total)
        off = max(abs(n / total - p) for n in counts.values()) / (6 * sd)
    return [("rows_missing_or_extra", abs(len(got) - len(want)),
             limits["rows_missing_or_extra"]),
            ("rows_differ", differ, limits["rows_differ"]),
            ("letters_not_upper", not_upper, limits["letters_not_upper"]),
            ("letter_share_off", off, limits["letter_share_off"])]


def answer_bytes(answer: list) -> int:
    """Bytes of the answer as a program must hand it over: string bytes as
    UTF-8 and 8 bytes for every other cell."""
    n = 0
    for row in answer:
        for v in row:
            n += len(v.encode()) if isinstance(v, str) else 8
    return n
