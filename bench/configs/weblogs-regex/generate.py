"""Synthetic web-server access logs in Apache Common Log Format, with the
block list of the weblogs pipeline.

    host ident user [dd/Mon/yyyy:hh:mm:ss -zzzz] "METHOD endpoint PROTO" status size

No real log is here, so every share below is from memory of university
web servers' logs and is listed under `assumed` in `weblogs-regex.json`.
One call makes one chunk of one table from its own `random.Random`, so a
chunk is the same whatever process makes it. Which lines are malformed,
come from a block-listed address, lack a size or name a user's home page
is drawn by *count* within a chunk (`round(n * share)` lines), uniformly
over the chunk, so every job has them and the head of a file is like the
rest of it. Addresses are functions of an index (client `k`, block-listed
address `j`) through one bijection of the 32-bit space, so the two sets
are disjoint and the logs chunks know the block list without reading it.
"""

from __future__ import annotations

import itertools
import math

COLUMNS = {"logs": ["logline"], "bad_ips": ["BadIPs"]}

_MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
           "Oct", "Nov", "Dec"]
_METHODS = (("GET", 0.90), ("POST", 0.08), ("HEAD", 0.02))
_STATUS = ((200, 0.76), (304, 0.08), (404, 0.06), (302, 0.04), (206, 0.02),
           (301, 0.015), (403, 0.01), (500, 0.005), (401, 0.005),
           (400, 0.005))
_WORDS = ["index.html", "images", "img", "css", "js", "static", "courses",
          "cs101", "math", "library", "news", "events", "admissions",
          "research", "people", "faculty", "pub", "papers", "2019", "lab",
          "logo.gif", "style.css", "main.js", "favicon.ico", "robots.txt",
          "wp-content", "uploads", "docs", "api", "v1", "search", "about",
          "calendar", "alumni", "sports", "photos", "lecture-notes",
          "handout.pdf", "syllabus.html", "hw3.pdf", "data", "archive"]
_QUERY_KEYS = ["q", "id", "page", "sort", "lang", "utm_source", "ref", "s",
               "session", "year"]
_NAMES = ["jsmith", "mlee", "akumar", "cchen", "rgarcia", "dnguyen",
          "pwilson", "hmueller", "tanaka", "obrien", "lrossi", "kpatel",
          "ewang", "fjohnson", "gsilva", "ikim", "bmartin", "vpetrov",
          "nali", "ydubois"]
_ALNUM = "abcdefghijklmnopqrstuvwxyz0123456789"
_BAD_MALFORMED = ['-', '\\x16\\x03\\x01\\x02\\x00\\x01\\x00\\x01\\xfc\\x03',
                  '\\x80w\\x01\\x03\\x01\\x00N\\x00\\x00\\x00',
                  'GET', 'OPTIONS', '\\x05\\x01\\x00', 'quit']

_ZIPF: dict = {}


def ip_of(index: int) -> str:
    """The address of index `index`: an odd multiplier and an offset make
    a bijection of the 32-bit space, so distinct indices give distinct
    addresses, scattered over it."""
    v = (index * 2654435761 + 0x0A3B1C07) & 0xFFFFFFFF
    return f"{v >> 24}.{v >> 16 & 255}.{v >> 8 & 255}.{v & 255}"


def zipf_cum(n: int, s: float) -> list:
    key = (n, s)
    if key not in _ZIPF:
        _ZIPF.clear()
        _ZIPF[key] = list(itertools.accumulate(
            1.0 / (r + 1) ** s for r in range(n)))
    return _ZIPF[key]


def _pick(rng, n_rows: int, share: float, taken: set) -> set:
    """`round(n_rows * share)` lines of the chunk that `taken` lacks."""
    free = [i for i in range(n_rows) if i not in taken]
    return set(rng.sample(free, min(len(free), round(n_rows * share))))


def _draw(rng, table) -> object:
    u = rng.random()
    for v, p in table:
        if u < p:
            return v
        u -= p
    return table[0][0]


def gen_chunk(table: str, rng, n_rows: int, first_row: int,
              params: dict) -> list:
    """`n_rows` rows of `table` as lists of strings, in COLUMNS order."""
    if table == "logs":
        return _logs(rng, n_rows, first_row, params)
    if table == "bad_ips":
        return [[ip_of(int(params["clients"]) + first_row + i)]
                for i in range(n_rows)]
    raise ValueError(f"weblogs-regex has no table {table!r}")


def _segment(rng) -> str:
    if rng.random() < 0.7:
        return rng.choice(_WORDS)
    return "".join(rng.choice(_ALNUM) for _ in range(rng.randint(3, 12)))


def _endpoint(rng, home: bool, query: bool, params: dict) -> str:
    """A path of about a lognormal length, 5 to 200 B (a tail of
    `long_tail` lines runs to 1,000 B), under `/~name` for a user's home
    page, with a query string where asked."""
    if rng.random() < float(params["long_tail"]):
        target = rng.randint(200, 1000)
    else:
        target = int(math.exp(rng.gauss(float(params["endpoint_mu"]),
                                         float(params["endpoint_sigma"]))))
        target = max(5, min(200, target))
    path = f"/~{rng.choice(_NAMES)}" if home else ""
    qs = ""
    if query:
        qs = "?" + "&".join(
            f"{rng.choice(_QUERY_KEYS)}="
            + "".join(rng.choice(_ALNUM) for _ in range(rng.randint(1, 16)))
            for _ in range(rng.randint(1, 3)))
    while len(path) + len(qs) < target:
        path += "/" + _segment(rng)
    path = path[:max(target - len(qs), 3 if home else 1)]
    if home and len(path) < 3:
        path = f"/~{rng.choice(_NAMES)}"
    return (path or "/") + qs


def _logs(rng, n_rows: int, first_row: int, params: dict) -> list:
    total = int(params["_rows"]["logs"])
    n_clients = int(params["clients"])
    n_bad = int(params["_rows"]["bad_ips"])
    cum = zipf_cum(n_clients, float(params["client_zipf"]))
    malformed = _pick(rng, n_rows, float(params["malformed"]), set())
    bad = _pick(rng, n_rows, float(params["bad_ip_share"]), set())
    home = _pick(rng, n_rows, float(params["home_pages"]), malformed)
    query = _pick(rng, n_rows, float(params["query_strings"]), malformed)
    no_size = _pick(rng, n_rows, float(params["no_size"]), set())
    user = _pick(rng, n_rows, 1.0 - float(params["ident_user_dash"]), set())
    day, month, year = (int(v) for v in params["date"].split("-")[::-1])
    zone = params["zone"]
    rows = []
    for i in range(n_rows):
        row = first_row + i
        if i in bad:
            ip = ip_of(n_clients + rng.randrange(n_bad))
        else:
            ip = ip_of(rng.choices(range(n_clients), cum_weights=cum)[0])
        if i in user:
            login = rng.choice(_NAMES)
            ident, uid = (login if rng.random() < 0.25 else "-"), login
        else:
            ident = uid = "-"
        sec = row * 86400 // max(total, 1)
        stamp = (f"[{day:02d}/{_MONTHS[month - 1]}/{year:04d}:"
                 f"{sec // 3600:02d}:{sec // 60 % 60:02d}:{sec % 60:02d} "
                 f"{zone}]")
        if i in malformed:
            request, status = rng.choice(_BAD_MALFORMED), 400
        else:
            method = _draw(rng, _METHODS)
            proto = "HTTP/1.1" if rng.random() < 0.7 else "HTTP/1.0"
            request = (f"{method} "
                       f"{_endpoint(rng, i in home, i in query, params)} "
                       f"{proto}")
            status = _draw(rng, _STATUS)
        if i in no_size:
            size = "-"
        else:
            size = str(min(int(math.exp(rng.gauss(8.5, 1.6))), 50_000_000))
        rows.append([f'{ip} {ident} {uid} {stamp} "{request}" {status} '
                     f"{size}"])
    return rows
