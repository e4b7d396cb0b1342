"""The KDD Cup 1999 table's shape from the seed (UCI dataset 130,
``kddcup.data``): 41 features of a network connection in the published
order, 3 of them symbolic, and the five categories the cup scored.

The file cannot be fetched on a sealed machine, so the rows are drawn: the
columns, their order, their types and level counts, and the class counts are
the published table's; the values stand in for it.  What a tree fit's speed
and its splits depend on is kept:

* ``protocol_type`` (3 levels), ``service`` (70) and ``flag`` (11) are
  categorical, each class drawing its levels from a distribution of its own
  that a few levels dominate, dealt to the codes independently of their
  order: the response depends on WHICH level a row has;
* the 38 numeric columns are of the published kinds: durations and byte
  counts (zero-inflated, heavy-tailed integers), 0/1 flags, small counts,
  connection counts of 0..511 and 0..255, and rates in steps of 0.01,
  ``num_outbound_cmds`` always 0;
* the classes come at the published counts, exactly, at the table's full
  size: DoS 3,883,370, normal 972,781, probe 41,102, R2L 1,126, U2R 52;
* most rows are exact duplicates: a class's rows are copies of its
  ``distinct`` prototype rows, each prototype once and the rest drawn with
  Zipf weights (a few, like the cup's smurf and neptune floods, repeated
  hundreds of thousands of times).  Exact duplicates matter to the quantile
  bins.

What the table IS (each class's level and value distributions) comes from
``shape_seed`` and is the same for every seed; the prototypes and their
copies come from ``--seed``.  At fewer rows than the published table the
class counts and the distinct counts are the published ones scaled, at
least one row of each class.

Values the source does not give, each an assumption (the configuration's
``assumed`` lists them): every numeric column's distribution a class, the
level distributions, the distinct rows a class (Tavallaee et al. 2009 give
1,074,992 distinct rows in all; the split over the classes is quoted from
memory), the Zipf exponent of the copies.  No NA: the source has none.
"""

import numpy as np

#: the published counts of the cup's five categories, in the order of the
#: response's codes
CLASSES = ("normal", "dos", "probe", "r2l", "u2r")
COUNTS = (972_781, 3_883_370, 41_102, 1_126, 52)
#: distinct rows a class (1,074,992 in all)
DISTINCT = (812_814, 247_267, 13_860, 999, 52)
ROWS = sum(COUNTS)

PROTOCOLS = ("icmp", "tcp", "udp")
SERVICES = (
    "IRC", "X11", "Z39_50", "aol", "auth", "bgp", "courier", "csnet_ns", "ctf",
    "daytime", "discard", "domain", "domain_u", "echo", "eco_i", "ecr_i", "efs",
    "exec", "finger", "ftp", "ftp_data", "gopher", "harvest", "hostnames", "http",
    "http_2784", "http_443", "http_8001", "imap4", "iso_tsap", "klogin", "kshell",
    "ldap", "link", "login", "mtp", "name", "netbios_dgm", "netbios_ns",
    "netbios_ssn", "netstat", "nnsp", "nntp", "ntp_u", "other", "pm_dump", "pop_2",
    "pop_3", "printer", "private", "red_i", "remote_job", "rje", "shell", "smtp",
    "sql_net", "ssh", "sunrpc", "supdup", "systat", "telnet", "tftp_u", "tim_i",
    "time", "urh_i", "urp_i", "uucp", "uucp_path", "vmnet", "whois",
)
FLAGS = ("OTH", "REJ", "RSTO", "RSTOS0", "RSTR", "S0", "S1", "S2", "S3", "SF", "SH")

#: (name, kind) of the 41 columns in the published order; a kind says how a
#: numeric column's values are drawn
COLUMNS = (
    ("duration", "seconds"), ("protocol_type", PROTOCOLS), ("service", SERVICES),
    ("flag", FLAGS), ("src_bytes", "bytes"), ("dst_bytes", "bytes"),
    ("land", "flag"), ("wrong_fragment", "count3"), ("urgent", "small"),
    ("hot", "small"), ("num_failed_logins", "count5"), ("logged_in", "flag"),
    ("num_compromised", "small"), ("root_shell", "flag"), ("su_attempted", "count2"),
    ("num_root", "small"), ("num_file_creations", "small"), ("num_shells", "count2"),
    ("num_access_files", "small"), ("num_outbound_cmds", "zero"),
    ("is_host_login", "flag"), ("is_guest_login", "flag"), ("count", "conn511"),
    ("srv_count", "conn511"), ("serror_rate", "rate"), ("srv_serror_rate", "rate"),
    ("rerror_rate", "rate"), ("srv_rerror_rate", "rate"), ("same_srv_rate", "rate"),
    ("diff_srv_rate", "rate"), ("srv_diff_host_rate", "rate"),
    ("dst_host_count", "conn255"), ("dst_host_srv_count", "conn255"),
    ("dst_host_same_srv_rate", "rate"), ("dst_host_diff_srv_rate", "rate"),
    ("dst_host_same_src_port_rate", "rate"), ("dst_host_srv_diff_host_rate", "rate"),
    ("dst_host_serror_rate", "rate"), ("dst_host_srv_serror_rate", "rate"),
    ("dst_host_rerror_rate", "rate"), ("dst_host_srv_rerror_rate", "rate"),
)

#: what a ``table`` entry may leave out
DEFAULTS = {"shape_seed": 1999, "copies_zipf": 1.2}


def columns(spec: dict):
    """The published column order; a categorical's values are its level
    codes, in the order of its domain (the levels' names sorted, as a
    parser gives them)."""
    return [{"name": name, "type": "cat", "domain": list(kind)}
            if isinstance(kind, tuple) else {"name": name, "type": "num"}
            for name, kind in COLUMNS]


def class_counts(rows: int):
    """(rows, distinct rows) of each class at ``rows`` rows: the published
    counts at the table's size, scaled below it (largest remainders, at
    least one row of each class, a class's distinct rows no more than its
    rows)."""
    if rows >= ROWS:
        return list(COUNTS), list(DISTINCT)

    def apportion(total, weights):
        share = np.asarray(weights, np.float64) * total / sum(weights)
        out = np.maximum(np.floor(share).astype(np.int64), 1)
        for i in np.argsort(-(share - np.floor(share)))[: max(0, total - out.sum())]:
            out[i] += 1
        return out

    counts = apportion(rows, COUNTS)
    counts[int(np.argmax(counts))] -= counts.sum() - rows
    distinct = np.minimum(apportion(max(1, round(rows * sum(DISTINCT) / ROWS)),
                                    DISTINCT), counts)
    return [int(v) for v in counts], [int(v) for v in distinct]


def _shape(spec: dict) -> list:
    """Each class's distributions: for a categorical, the probabilities of
    its levels (a few dominant, dealt to codes at random); for a numeric
    column, the share of nonzero values and a scale."""
    rng = np.random.default_rng(int(spec["shape_seed"]))
    out = []
    for _ in CLASSES:
        cls = []
        for _, kind in COLUMNS:
            if isinstance(kind, tuple):
                w = rng.dirichlet(np.full(len(kind), 0.3))
                cls.append(w[rng.permutation(len(kind))])
            else:
                cls.append((rng.beta(0.6, 0.9), rng.uniform(0.2, 1.0)))
        out.append(cls)
    return out


def _draw(rng, kind, shape, n: int) -> np.ndarray:
    """n values of a column of ``kind`` for one class."""
    if isinstance(kind, tuple):
        return rng.choice(len(kind), size=n, p=shape).astype(np.float32)
    nonzero, scale = shape
    on = rng.random(n) < nonzero
    if kind == "zero":
        return np.zeros(n, np.float32)
    if kind == "flag":
        return on.astype(np.float32)
    if kind == "seconds":
        v = np.floor(rng.lognormal(2.0 + 6.0 * scale, 1.5, n))
    elif kind == "bytes":
        v = np.floor(rng.lognormal(3.0 + 8.0 * scale, 1.8, n))
    elif kind.startswith("count"):
        v = rng.integers(1, int(kind[5:]) + 1, n)
    elif kind == "small":
        v = 1 + rng.poisson(30.0 * scale ** 3, n)
    elif kind.startswith("conn"):
        top = int(kind[4:])
        v = np.minimum(np.floor(rng.exponential(top * scale, n)) + 1, top)
    else:  # rate: steps of 0.01, many exactly 1 where on
        v = np.where(rng.random(n) < scale, 1.0,
                     np.round(rng.beta(2.0 * scale, 2.0, n), 2))
    return np.where(on, v, 0.0).astype(np.float32)


def _distinct(rng, shape, n: int) -> np.ndarray:
    """n pairwise distinct prototype rows of one class, [n, 41] float32."""
    have = np.zeros((0, len(COLUMNS)), np.float32)
    mix = rng.integers(1, 2**61, len(COLUMNS), dtype=np.int64) | 1
    while len(have) < n:
        want = n - len(have) + max(16, (n - len(have)) // 4)
        new = np.stack([_draw(rng, kind, shape[j], want)
                        for j, (_, kind) in enumerate(COLUMNS)], axis=1)
        rows = np.concatenate([have, new])
        key = (rows.view(np.uint32).astype(np.int64) * mix).sum(axis=1)
        _, first = np.unique(key, return_index=True)
        have = rows[np.sort(first)][:n]
    return have


def make(spec: dict, rows: int, seed: int):
    """(X float32 [rows, 41], y int32 in 0..4) for a configuration's
    ``table`` entry: the rows of each class copies of its prototypes, the
    classes mixed in one random order."""
    if int(spec["classes"]) != len(CLASSES) or int(spec["features"]) != len(COLUMNS):
        raise SystemExit("kdd99-synth makes 41 features and 5 classes")
    spec = {**DEFAULTS, **spec}
    shape = _shape(spec)
    rng = np.random.default_rng(seed)
    counts, distinct = class_counts(rows)
    X = np.empty((rows, len(COLUMNS)), np.float32)
    y = np.empty(rows, np.int32)
    at = 0
    for c, (n, k) in enumerate(zip(counts, distinct)):
        protos = _distinct(rng, shape[c], k)
        w = np.arange(1, k + 1, dtype=np.float64) ** -float(spec["copies_zipf"])
        copies = rng.choice(k, size=n - k, p=w / w.sum())
        pick = np.concatenate([np.arange(k), rng.permutation(k)[copies]])
        X[at:at + n] = protos[pick]
        y[at:at + n] = c
        at += n
    order = rng.permutation(rows)
    return X[order], y[order]
