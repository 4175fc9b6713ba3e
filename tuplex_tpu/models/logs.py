"""Apache-log pipelines of the paper's weblogs benchmark (Tuplex, SIGMOD'21,
section 6.1; upstream benchmarks/logs/runtuplex.py).

Upstream's script parses each Common Log Format line of `ctx.text` into
a dict row (regex mode: one anchored `re.search` with nine groups,
`ParseWithRegex`; strip mode: find/slice chains, `ParseWithStrip`),
anonymizes users' home pages `/~name...` with ten random capitals
(`randomize_udf`, an anchored `re.sub`), inner-joins the rows with a
bad-IP list on the address and selects seven columns
(`build_malicious_pipeline`). `build_pipeline` is the parse alone with a
`len(endpoint) > 0` filter.

Both parse variants compile to the device: the strip variant as
find/slice chains + dict row, and the regex variant through the compiled
re.search subset (ops/regex.py lowers the anchored pattern to whole-column
kernel steps; ops/nfa.py decides the rows the one-level backtracking
cannot, and ops/pallas_nfa.py is its Pallas form). `randomize_udf`
compiles as an anchored re.sub whose replacement is a per-row string
drawn by the program's own generator (not CPython's sequence). Rows the
compiled matcher cannot decide fail-safe to the interpreter — it never
succeeds with a different answer than CPython's re.
"""

from __future__ import annotations

import random
import re
import string

COLUMNS = ["ip", "client_id", "user_id", "date", "method", "endpoint",
           "protocol", "response_code", "content_size"]


def ParseWithStrip(x):
    y = x

    i = y.find(" ")
    ip = y[:i]
    y = y[i + 1:]

    i = y.find(" ")
    client_id = y[:i]
    y = y[i + 1:]

    i = y.find(" ")
    user_id = y[:i]
    y = y[i + 1:]

    i = y.find("]")
    date = y[:i][1:]
    y = y[i + 2:]

    y = y[y.find('"') + 1:]

    method = ""
    endpoint = ""
    protocol = ""
    failed = False
    if y.find(" ") < y.rfind('"'):
        i = y.find(" ")
        method = y[:i]
        y = y[i + 1:]

        i = y.find(" ")
        endpoint = y[:i]
        y = y[i + 1:]

        i = y.rfind('"')
        protocol = y[:i]
        protocol = protocol[protocol.rfind(" ") + 1:]
        y = y[i + 2:]
    else:
        failed = True
        i = y.rfind('"')
        y = y[i + 2:]

    i = y.find(" ")
    response_code = y[:i]
    content_size = y[i + 1:]

    if not failed:
        return {"ip": ip,
                "client_id": client_id,
                "user_id": user_id,
                "date": date,
                "method": method,
                "endpoint": endpoint,
                "protocol": protocol,
                "response_code": int(response_code),
                "content_size": 0 if content_size == "-" else
                int(content_size)}
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


MALICIOUS_COLUMNS = ["ip", "date", "method", "endpoint", "protocol",
                     "response_code", "content_size"]


def build_pipeline(ds, mode: str = "strip"):
    """map(parse).filter(len(endpoint) > 0) over a dataset of lines."""
    fn = ParseWithStrip if mode == "strip" else ParseWithRegex
    return ds.map(fn).filter(lambda x: len(x["endpoint"]) > 0)


def build_malicious_pipeline(ctx, log_path: str, bad_ip_path: str):
    """reference: runtuplex.py, regex mode — the log's lines parsed, the
    home pages anonymized, the requests from block-listed addresses kept
    (the bad-IP file: a CSV with one column `BadIPs`)."""
    df = ctx.text(log_path).map(ParseWithRegex)
    df = df.withColumn("endpoint", lambda x: randomize_udf(x["endpoint"]))
    bad_ip_df = ctx.csv(bad_ip_path)
    df = df.join(bad_ip_df, "ip", "BadIPs")
    return df.selectColumns(MALICIOUS_COLUMNS)


# ---------------------------------------------------------------------------

_METHODS = ["GET", "POST", "HEAD"]
_ENDPOINTS = ["/index.html", "/images/logo.gif", "/about", "/~user/page",
              "/api/v1/items", "/search?q=x"]


def gen_logline(rng: random.Random) -> str:
    if rng.random() < 0.03:   # malformed request line
        return (f"{rng.randint(1,255)}.{rng.randint(0,255)}.0.1 - - "
                f"[01/Jul/1995:00:00:0{rng.randint(0,9)} -0400] "
                f'"garbage" 400 -')
    ip = f"{rng.randint(1,255)}.{rng.randint(0,255)}.{rng.randint(0,255)}.{rng.randint(1,254)}"
    size = rng.choice(["-", str(rng.randint(100, 99999))])
    return (f"{ip} - - [0{rng.randint(1,9)}/Jul/1995:12:{rng.randint(10,59)}:"
            f"{rng.randint(10,59)} -0400] "
            f'"{rng.choice(_METHODS)} {rng.choice(_ENDPOINTS)} HTTP/1.0" '
            f"{rng.choice([200, 200, 200, 304, 404])} {size}")


def generate_log(path: str, n: int, seed: int = 17) -> str:
    rng = random.Random(seed)
    with open(path, "w") as fp:
        for _ in range(n):
            fp.write(gen_logline(rng) + "\n")
    return path


def generate_bad_ips(path: str, log_path: str, n: int = 20,
                     seed: int = 17) -> list:
    """A bad-IP list of `n` addresses: half drawn from the log's lines,
    half that no line has."""
    rng = random.Random(seed)
    with open(log_path) as fp:
        seen = sorted({line.split(" ", 1)[0] for line in fp})
    ips = rng.sample(seen, min(len(seen), n - n // 2))
    ips += [f"10.255.{i}.{rng.randint(1, 254)}" for i in range(n // 2)]
    with open(path, "w") as fp:
        fp.write("BadIPs\n" + "".join(ip + "\n" for ip in ips))
    return ips


def run_reference_malicious(log_path: str, bad_ip_path: str) -> list:
    """The malicious-requests chain in plain CPython (the ten letters of
    an anonymized home page are CPython's draw, which the program's
    differ from by design)."""
    with open(bad_ip_path) as fp:
        bad = {line.strip() for line in list(fp)[1:]}
    out = []
    with open(log_path) as fp:
        for line in fp:
            try:
                d = ParseWithRegex(line.rstrip("\n"))
                d["endpoint"] = randomize_udf(d["endpoint"])
            except Exception:
                continue
            if d["ip"] in bad:
                out.append(tuple(d[c] for c in MALICIOUS_COLUMNS))
    return out


def run_reference_python(path: str, mode: str = "strip") -> list:
    fn = ParseWithStrip if mode == "strip" else ParseWithRegex
    out = []
    with open(path) as fp:
        for line in fp:
            line = line.rstrip("\n")
            try:
                d = fn(line)
                if len(d["endpoint"]) > 0:
                    out.append(tuple(d[c] for c in COLUMNS))
            except Exception:
                continue
    return out
