"""The KDD Cup 1999 intrusion-detection table's shape, made from
``--seed`` on the host.

The real table (`kddcup.data`, the UCI KDD archive: 4,898,431
connection records of a simulated Air Force network) has 41 predictors
— three symbolic ones, `protocol_type` (3 levels), `service` (70) and
`flag` (11), and 38 numeric ones: heavy-tailed byte counts and
durations, 0/1 flags, small counts, connection counts of 0-511 and
0-255, rates on a 0.01 grid, and one column that is always 0
(`num_outbound_cmds`) — and a response of 23 classes, the normal
traffic and 22 attacks, whose published counts run from 2,807,886
(`smurf`) down to 2 (`spy`). This file keeps the columns in their
order, kinds and ranges, the three symbolic columns with their level
names (handed over as integer codes with their domains, what parsing
the CSV leaves; the levels in sorted order, as H2O-3 gives them), the
skew of the services, and the classes at their published counts —
exactly at the full size, scaled by the rows where the table is cut,
never under one row a class. Not the rows: a row's class is drawn
first, in those counts, and its connection after it, so that the
class hangs on its service, flag and protocol (each class has its own
services, drawn from a fixed profile over the services' popularity; a
tcp connection its own flags; the icmp and udp services fix the
protocol, and `private` / `other` are tcp or udp by class) and on its
numeric columns (each class has its own rate of every 0/1 flag, its
own scale of the byte counts, the connection counts and the rates),
with the signatures of the large attacks: `smurf`'s echo replies of
520 or 1,032 bytes at a count near 511, `neptune`'s half-open
connections (S0, error rates near 1), `land`'s equal addresses,
`teardrop`'s and `pod`'s wrong fragments.

A row's connection depends on (seed, chunk index) alone, as in
`datasets.higgs_like`; the classes' places among the rows are one
permutation of the whole table, drawn from the seed."""

from __future__ import annotations

import numpy as np

CHUNK_ROWS = 1 << 20
ROWS = 4_898_431                 # the published table

PROTOCOLS = ("icmp", "tcp", "udp")
SERVICES = tuple(sorted((
    "aol auth bgp courier csnet_ns ctf daytime discard domain domain_u "
    "echo eco_i ecr_i efs exec finger ftp ftp_data gopher harvest "
    "hostnames http http_2784 http_443 http_8001 imap4 IRC iso_tsap "
    "klogin kshell ldap link login mtp name netbios_dgm netbios_ns "
    "netbios_ssn netstat nnsp nntp ntp_u other pm_dump pop_2 pop_3 "
    "printer private red_i remote_job rje shell smtp sql_net ssh sunrpc "
    "supdup systat telnet tftp_u tim_i time urh_i urp_i uucp uucp_path "
    "vmnet whois X11 Z39_50").split()))
FLAGS = ("OTH", "REJ", "RSTO", "RSTOS0", "RSTR", "S0", "S1", "S2", "S3",
         "SF", "SH")
# the published counts of `kddcup.data`, by class
COUNTS = {"smurf": 2_807_886, "neptune": 1_072_017, "normal": 972_781,
          "satan": 15_892, "ipsweep": 12_481, "portsweep": 10_413,
          "nmap": 2_316, "back": 2_203, "warezclient": 1_020,
          "teardrop": 979, "pod": 264, "guess_passwd": 53,
          "buffer_overflow": 30, "land": 21, "warezmaster": 20,
          "imap": 12, "rootkit": 10, "loadmodule": 9, "ftp_write": 8,
          "multihop": 7, "phf": 4, "perl": 3, "spy": 2}
CLASSES = tuple(sorted(COUNTS))
K = len(CLASSES)                 # 23

# (name, kind), in the real table's column order. Kinds: "enum" (its
# levels above), "bytes" (heavy-tailed integers), "duration" (seconds,
# mostly 0), "binary", "small" (integers 0 to the given top, mostly 0),
# "conn" (connections of the last two seconds, 0-511), "host"
# (connections to the host, 0-255), "rate" (0-1 on a 0.01 grid),
# "zero" (always 0)
COLUMNS = (
    ("duration", "duration"), ("protocol_type", "enum"),
    ("service", "enum"), ("flag", "enum"), ("src_bytes", "bytes"),
    ("dst_bytes", "bytes"), ("land", "binary"),
    ("wrong_fragment", ("small", 3)), ("urgent", ("small", 14)),
    ("hot", ("small", 77)), ("num_failed_logins", ("small", 5)),
    ("logged_in", "binary"), ("num_compromised", ("small", 7479)),
    ("root_shell", "binary"), ("su_attempted", ("small", 2)),
    ("num_root", ("small", 7468)), ("num_file_creations", ("small", 43)),
    ("num_shells", ("small", 2)), ("num_access_files", ("small", 9)),
    ("num_outbound_cmds", "zero"), ("is_host_login", "binary"),
    ("is_guest_login", "binary"), ("count", "conn"),
    ("srv_count", "conn"), ("serror_rate", "rate"),
    ("srv_serror_rate", "rate"), ("rerror_rate", "rate"),
    ("srv_rerror_rate", "rate"), ("same_srv_rate", "rate"),
    ("diff_srv_rate", "rate"), ("srv_diff_host_rate", "rate"),
    ("dst_host_count", "host"), ("dst_host_srv_count", "host"),
    ("dst_host_same_srv_rate", "rate"),
    ("dst_host_diff_srv_rate", "rate"),
    ("dst_host_same_src_port_rate", "rate"),
    ("dst_host_srv_diff_host_rate", "rate"),
    ("dst_host_serror_rate", "rate"),
    ("dst_host_srv_serror_rate", "rate"),
    ("dst_host_rerror_rate", "rate"),
    ("dst_host_srv_rerror_rate", "rate"))
NAMES = tuple(n for n, _ in COLUMNS)
N_FEATURES = len(COLUMNS)        # 41
DOMAINS = {"protocol_type": PROTOCOLS, "service": SERVICES,
           "flag": FLAGS}
LEVELS = np.array([len(DOMAINS.get(n, ())) for n in NAMES],
                  dtype=np.int64)

ICMP = ("eco_i", "ecr_i", "red_i", "tim_i", "urh_i", "urp_i")
UDP = ("domain_u", "ntp_u", "tftp_u")
EITHER = ("other", "private")    # tcp or udp, by class

# What every table shares: each class's profile, from a fixed draw.
_FIXED = np.random.default_rng(0x6DD99)
# the services' popularity: rank r holds a share that falls off as
# 1 / (r + 1); a fixed shuffle says which service has which rank
_POP = 1.0 / np.arange(1, len(SERVICES) + 1)
_POP = _POP[_FIXED.permutation(len(SERVICES))]


def _service_profile(c: str) -> np.ndarray:
    """Class ``c``'s probability of each service: the popularity times
    an affinity of the class's own (a few services carry a class), and
    the large classes' published signatures."""
    pin = {"smurf": {"ecr_i": 0.997}, "pod": {"ecr_i": 0.8},
           "neptune": {"private": 0.75}, "satan": {"other": 0.3},
           "ipsweep": {"eco_i": 0.9}, "nmap": {"private": 0.4},
           "normal": {"http": 0.6, "smtp": 0.1, "domain_u": 0.06,
                      "ftp_data": 0.05}, "back": {"http": 0.99},
           "teardrop": {"private": 0.99}, "land": {"finger": 0.5},
           "warezclient": {"ftp_data": 0.7}, "warezmaster": {"ftp": 0.6},
           "guess_passwd": {"telnet": 0.95}, "imap": {"imap4": 0.95},
           "phf": {"http": 0.99}}.get(c, {"telnet": 0.5})
    w = _POP * np.exp(_FIXED.normal(0.0, 2.0, len(SERVICES)))
    w = w / w.sum() * (1.0 - sum(pin.values()))
    for s, p in pin.items():
        w[SERVICES.index(s)] += p
    return w / w.sum()


def _flag_profile(c: str) -> np.ndarray:
    """Class ``c``'s probability of each flag of a tcp connection."""
    pin = {"neptune": {"S0": 0.9, "REJ": 0.08},
           "portsweep": {"REJ": 0.5, "RSTR": 0.3},
           "satan": {"REJ": 0.6, "S0": 0.1}, "nmap": {"S0": 0.3},
           "land": {"S0": 0.9}}.get(c, {"SF": 0.95})
    w = np.exp(_FIXED.normal(0.0, 1.5, len(FLAGS)))
    w = w / w.sum() * (1.0 - sum(pin.values()))
    for f, p in pin.items():
        w[FLAGS.index(f)] += p
    return w / w.sum()


SERVICE_P = np.stack([_service_profile(c) for c in CLASSES])   # [K, 70]
FLAG_P = np.stack([_flag_profile(c) for c in CLASSES])         # [K, 11]
UDP_P = _FIXED.uniform(0.0, 1.0, K)      # `private` / `other` over udp
# a class's own scale of every numeric column: a logit for the 0/1 and
# zero-inflated columns, a log-mean for the counts and byte counts, a
# mean for the rates (and how often a rate is exactly 0 or 1)
_NUM = [j for j, (_, kind) in enumerate(COLUMNS) if kind != "enum"]
LOGIT = _FIXED.normal(-4.5, 1.5, (K, N_FEATURES))
SCALE = _FIXED.normal(0.0, 1.0, (K, N_FEATURES))
RATE = _FIXED.uniform(0.0, 1.0, (K, N_FEATURES))
for _c, _col, _v in (("neptune", "serror_rate", 0.98),
                     ("neptune", "srv_serror_rate", 0.98),
                     ("neptune", "dst_host_serror_rate", 0.98),
                     ("smurf", "same_srv_rate", 0.999),
                     ("portsweep", "rerror_rate", 0.9)):
    RATE[CLASSES.index(_c), NAMES.index(_col)] = _v
for _c, _col in (("land", "land"), ("teardrop", "wrong_fragment"),
                 ("pod", "wrong_fragment"), ("normal", "logged_in"),
                 ("back", "logged_in"), ("warezclient", "is_guest_login")):
    LOGIT[CLASSES.index(_c), NAMES.index(_col)] = 6.0


def class_counts(rows: int) -> np.ndarray:
    """[K] rows of each class: the published counts scaled to ``rows``
    (exactly the published ones at `ROWS`), at least one a class; the
    largest class takes what rounding leaves."""
    want = np.array([COUNTS[c] for c in CLASSES], dtype=np.float64)
    n = np.maximum(np.rint(want * rows / ROWS), 1).astype(np.int64)
    n[np.argmax(want)] += rows - n.sum()
    return n


def _connections(y: np.ndarray, rng) -> np.ndarray:
    """[41, rows] float32: the connection of a row of each class in
    ``y``."""
    rows = len(y)
    X = np.zeros((N_FEATURES, rows), dtype=np.float32)
    u = rng.random((N_FEATURES, rows))
    z = rng.standard_normal((N_FEATURES, rows))
    service = np.empty(rows, dtype=np.int64)
    flag = np.empty(rows, dtype=np.int64)
    for k in range(K):
        at = np.flatnonzero(y == k)
        service[at] = np.minimum(np.searchsorted(
            np.cumsum(SERVICE_P[k]), u[2, at]), len(SERVICES) - 1)
        flag[at] = np.minimum(np.searchsorted(
            np.cumsum(FLAG_P[k]), u[3, at]), len(FLAGS) - 1)
    name = np.array(SERVICES)[service]
    proto = np.where(np.isin(name, ICMP), 0,
                     np.where(np.isin(name, UDP), 2, 1))
    either = np.isin(name, EITHER)
    proto = np.where(either & (u[1] < UDP_P[y]), 2, proto)
    flag = np.where(proto == 1, flag, FLAGS.index("SF"))
    X[1], X[2], X[3] = proto, service, flag
    for j in _NUM:
        kind = COLUMNS[j][1]
        on = u[j] < 1.0 / (1.0 + np.exp(-LOGIT[y, j]))
        s = SCALE[y, j]
        if kind == "binary":
            x = on
        elif kind == "duration":
            x = on * np.minimum(np.rint(np.exp(4.0 + 2.0 * s + 1.5 * z[j])),
                                58_329)
        elif kind == "bytes":
            x = np.rint(np.exp(6.0 + 2.0 * s + 1.2 * z[j]))
        elif kind == "conn":
            x = np.clip(np.rint(np.exp(3.0 + 1.5 * s + 0.8 * z[j])), 0, 511)
        elif kind == "host":
            x = np.clip(np.rint(np.exp(4.0 + 1.2 * s + 0.6 * z[j])), 0, 255)
        elif kind == "rate":
            m = RATE[y, j]
            x = np.where(u[j] < m * m, 1.0, np.where(
                u[j] > 1.0 - (1.0 - m) ** 2, 0.0,
                np.clip(np.rint(100.0 * (m + 0.1 * z[j])) / 100.0, 0, 1)))
        elif kind == "zero":
            x = 0.0
        else:                                          # ("small", top)
            x = on * np.minimum(np.rint(np.exp(s + z[j])), kind[1])
        X[j] = x
    smurf = y == CLASSES.index("smurf")
    X[NAMES.index("src_bytes"), smurf] = np.where(
        u[4, smurf] < 0.7, 1032.0, 520.0)
    X[NAMES.index("count"), smurf] = np.clip(511 - np.rint(
        np.abs(z[22, smurf]) * 3), 0, 511)
    return X


def kddcup99_like(rows: int, seed: int):
    """(X [41, rows] float32, one contiguous row per column, the
    symbolic columns' level codes as numbers; y [rows] int8, the class
    index into `CLASSES`). The same (rows, seed) gives the same table."""
    y = np.repeat(np.arange(K, dtype=np.int8), class_counts(rows))
    y = y[np.random.default_rng([int(seed), 0xC1A55]).permutation(rows)]
    X = np.empty((N_FEATURES, rows), dtype=np.float32)
    for c, lo in enumerate(range(0, rows, CHUNK_ROWS)):
        hi = min(lo + CHUNK_ROWS, rows)
        X[:, lo:hi] = _connections(y[lo:hi],
                                   np.random.default_rng([int(seed), c]))
    return X, y


def domains() -> dict[str, list[str]]:
    """The level names of the symbolic columns and of the response,
    code = position."""
    return {**{n: list(d) for n, d in DOMAINS.items()}, "y": list(CLASSES)}


def as_columns(X: np.ndarray, y: np.ndarray) -> dict[str, np.ndarray]:
    """The table as a user hands it to `Frame.from_arrays` beside
    `domains()`: the symbolic columns and the class as int32 codes, the
    numeric ones float32."""
    cols = {name: X[j].astype(np.int32) if LEVELS[j] else X[j]
            for j, name in enumerate(NAMES)}
    cols["y"] = y.astype(np.int32)
    return cols
